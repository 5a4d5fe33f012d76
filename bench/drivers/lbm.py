"""Driver of the LBM cells: ``lbm_run`` on a lattice made from the seed.

Set-up makes the lattice on the device in one jitted call (sharded along
X over a ``data`` mesh axis when the cell has several chips), and warms
the one program the window calls: ``lbm_run`` for ``sweeps_per_call``
sweeps, its lattice donated so that each call writes over the one it
read.  The window calls it back to back on its own output, keeping the
workload file's ``ahead_s`` seconds of calls in flight ahead of the one
it waits for, so that a host that stands still for less than that leaves
the chip busy.  When ``--seconds`` have passed it sends no more calls,
waits for all that were sent, and then closes: the rate counts every
sweep of every call over the whole window.

What is compared: the output of the window's first call, at planes
sampled from the seed (both sides of every shard cut, where the halo
exchange matters, and two drawn at random), against the reference run
from the same starting lattice.  A plane after ``K`` sweeps depends only
on the ``2K + 1`` planes around it, so the reference runs on those slabs.
The state after the window must also be finite everywhere.
"""
from __future__ import annotations

import collections
import contextlib
import math
import sys
import time

import numpy as np

# The numbers compared; each workload file gives their limits ("limits").
CHECKS = ("lbm_max_rel_err", "lbm_nonfinite_sites")


def sample_planes(nx: int, chips: int, seed: int) -> list[int]:
    """Both planes at every shard cut (x = 0 included: the periodic
    wrap), and two more drawn from the seed."""
    per = nx // chips
    cuts = sorted({(c * per - 1) % nx for c in range(chips)}
                  | {c * per for c in range(chips)})
    rng = np.random.default_rng(seed)
    extra = [int(x) for x in rng.choice(nx, size=2, replace=False)]
    return sorted(set(cuts) | set(extra))


def rel_err(got, want) -> float:
    """Largest relative error of any population at any site."""
    return float(np.max(np.abs(got - want) / np.abs(want)))


def run(run):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import harness
    import stats
    from repro import api
    from repro.kernels.lbm import ops as lbm_ops

    cell, cfg = run.cell, run.config
    ref = harness.reference(cell["config"])
    chips = cell["chips"]
    x, y, z = cfg["lattice_per_chip"]
    shape = (ref.Q, x * chips, y, z)
    sweeps = int(cell["sweeps_per_call"])
    omega = float(cfg["omega"])
    layout = cfg["layout"]
    key = harness.seed_key(run.seed)

    sharding, plan_ctx = None, contextlib.nullcontext()
    if chips > 1:
        mesh = Mesh(np.array(run.devices).reshape(cell["mesh"]),
                    ("data", "model"))
        sharding = NamedSharding(mesh, PartitionSpec(None, "data", None,
                                                     None))
        plan_ctx = api.plan_context(mesh=mesh)
    make = jax.jit(lambda k: ref.make_lattice(k, shape),
                   out_shardings=sharding)
    planes = jnp.asarray(sample_planes(shape[1], chips, run.seed))
    take = jax.jit(lambda f: jnp.take(f, planes, axis=1))
    count_bad = jax.jit(lambda f: jnp.sum(~jnp.isfinite(f)))

    # Under the benchmark's own jit: ``lbm_run`` under a mesh builds a
    # new jitted loop on every call, which would trace it again inside
    # the window (PERF.md, Open questions).  Besides the lattice a call
    # returns one site of it, which the window waits on: the lattice
    # itself is donated to the next call.
    def step(f):
        g = lbm_ops.lbm_run(f, omega, sweeps, layout=layout)
        return g, g[0, 0, 0, 0]

    call = jax.jit(step, donate_argnums=0)

    with plan_ctx:
        # Warm-up: every program the window and its checks use, and one
        # call timed, to size the calls kept in flight.
        f, token = call(make(key))
        take(f).block_until_ready()
        count_bad(f).block_until_ready()
        t = time.perf_counter()
        f, token = call(f)
        token.block_until_ready()
        call_s = time.perf_counter() - t
        ahead = math.ceil(float(cell.get("ahead_s", 0.0)) / call_s)
        del f, token
        f = make(key)
        f.block_until_ready()

        window = run.window
        calls = traced_calls = 0
        first = None
        pending = collections.deque()
        longest_wait = longest_host = 0.0

        def drain():
            while pending:
                pending.popleft().block_until_ready()

        window.open()
        last = window.opened
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                f, token = call(f)
                if first is None:
                    first = take(f)
            pending.append(token)
            calls += 1
            t = time.perf_counter()
            while len(pending) > ahead:
                pending.popleft().block_until_ready()
            now = time.perf_counter()
            longest_wait = max(longest_wait, now - t)
            longest_host = max(longest_host, t - last)
            last = now
            if window.tracing:
                traced_calls += 1
                # The trace holds whole calls: those sent into it cover
                # its length, and it ends once they have run.
                if max(now - window.opened,
                       traced_calls * call_s) >= window.trace_s:
                    drain()
                    window.stop_trace()
            if now - window.opened >= run.seconds:
                break
        drain()
        window.close()
        nonfinite = int(count_bad(f))
        run.note_memory()
        got = np.asarray(first)
        del f, first

        # The reference, from the same lattice, on the sampled slabs.
        f0 = make(key)
        nx = shape[1]
        idx = jnp.asarray([ref.slab_indices(int(p), sweeps, nx)
                           for p in np.asarray(planes)])
        slabs = jax.device_put(
            jax.jit(lambda f: jnp.take(f, idx.reshape(-1), axis=1))(f0),
            run.devices[0])
        del f0
    slabs = slabs.reshape(ref.Q, len(planes), 2 * sweeps + 1, y, z)
    center = jax.jit(lambda s: ref.center_plane(s, omega, sweeps))
    # The control (bench/control.py): the reference in bfloat16 in the
    # program's place.  The benchmark's own runs never compute it.
    low = jax.jit(lambda s: ref.center_plane(s, omega, sweeps, jnp.bfloat16))
    err = control = 0.0
    for i in range(len(planes)):
        want = np.asarray(center(slabs[:, i]))
        err = max(err, rel_err(got[:, i], want))
        if run.info.get("control"):
            control = max(control, rel_err(np.asarray(low(slabs[:, i])),
                                           want))
    sites = shape[1] * y * z
    mlups = stats.rate(calls * sweeps * sites, window.seconds) / 1e6
    print(f"bench: {calls} calls of {sweeps} sweeps in {window.seconds:.3f}"
          f" s", file=sys.stderr)
    return harness.Outcome(
        metrics={"lbm_mlups": mlups},
        checks=harness.checks(cell, {"lbm_max_rel_err": err,
                                     "lbm_nonfinite_sites": nonfinite}),
        attempted=calls, failed=0,
        info={"sweeps_per_call": sweeps, "traced_calls": traced_calls,
              "sites_per_chip": sites // chips, "chips": chips,
              "control": control,
              "report": {"calls": calls, "calls_ahead": ahead,
                         "warm_call_s": call_s,
                         "longest_wait_s": longest_wait,
                         "longest_host_s": longest_host,
                         "planes_compared":
                         [int(p) for p in np.asarray(planes)]}})
