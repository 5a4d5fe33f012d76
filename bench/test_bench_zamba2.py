"""The zamba2-7b configuration and its cells as the harness reads them, its
reference's operation and byte counts against hand counts, the two readers
it brings, and the four-chip LBM cell listed beside it."""
from __future__ import annotations

import math
import os
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

T = harness.trace_module()
REF = harness.reference("zamba2-7b")

# Every count below is small enough to check by hand: d 4, d_inner 8, two
# heads of 4 with state 2 in one group, conv 2, attention 2 x 4 over the
# 8-wide concat, MLP 6, adapter rank 1, vocabulary 10, three layers with a
# shared application before layer 1.
TINY = dict(
    harness.config("zamba2-7b"), hidden_size=4, mamba_expand=2,
    n_mamba_heads=2, mamba_headdim=4, mamba_d_state=2, mamba_ngroups=1,
    mamba_d_conv=2, num_attention_heads=2, num_key_value_heads=2,
    attention_head_dim=4, intermediate_size=6, adapter_rank=1,
    vocab_size=10, num_hidden_layers=3, hybrid_layer_ids=[1],
    num_mem_blocks=1)


def test_cells_and_configuration_load():
    bench = harness.benchmark()
    cell = harness.cell("zamba2-7b.chat", bench)
    assert cell["driver"] == "serve" and cell["chips"] == 1
    mix = cell["traffic_mix"]
    assert (mix["slots"], mix["max_len"], mix["prefill_chunk"]) == (32, 1024,
                                                                     1)
    assert set(cell["limits"]) == set(harness.driver("serve").CHECKS)
    cfg = harness.config(cell["config"])
    entry = {c["name"]: c for c in bench["configs"]}["zamba2-7b"]
    assert entry["reduced"] == cfg["reduced"]
    assert cfg["num_hidden_layers"] == len(cfg["layers_block_type"]) == 12
    assert cfg["hybrid_layer_ids"] == [
        i for i, t in enumerate(cfg["layers_block_type"]) if t == "hybrid"]
    lbm = harness.cell("lbm-d3q19.2048x256x256.4chip", bench)
    assert lbm["chips"] == 4 and lbm["mesh"] == [4, 1]
    assert "lbm.exposed_comm_ms" in [
        m["name"] for m in harness.per_layer_metrics(bench, lbm["name"])]


def test_model_config_keeps_the_published_widths():
    cfg = harness.config("zamba2-7b")
    mc = REF.model_config(cfg)
    assert (mc.d_model, mc.hd, mc.n_heads, mc.d_ff) == (3584, 224, 32, 14336)
    assert (mc.ssm_ngroups, mc.adapter_rank, mc.num_mem_blocks) == (2, 128, 2)
    assert mc.hybrid_ids == (6, 11) and mc.act == "gelu_exact"
    assert [k for k, _ in mc.stages()] == ["mamba", "shared_attn", "mamba",
                                           "shared_attn", "mamba"]


def test_flops_and_bytes_match_a_hand_count():
    # Weights a token multiplies by: 3 mixers of 4 * (2 * 8 + 2 + 2) + 8 * 4
    # = 120; one application of 8 * 6 * 4 + 2 * 4 * 4 (attention) + 3 * 4
    # * 6 (MLP) + 4 + 12 (adapter) + 16 (linear) = 328; the head 40.
    assert REF.matmul_params(TINY) == 3 * 120 + 328 + 40
    # (2 * 728 + 5 * 3 * 2 * 4 * 2) per row, 4 * 8 per key.
    flops = REF.decode_flops(TINY, 3, 10)
    assert isinstance(flops, int) and flops == (2 * 728 + 240) * 3 + 32 * 10
    # Weights: 3 mixers of 168 bf16 and 6 float32 elements (360 B), the
    # application's 32 elements, the shared block's 308, the embedding and
    # final norm's 44: 1848 B.  K/V: 32 B a position.  State: 3 mixers of
    # 1 * 12 conv inputs (bf16) and 2 * 4 * 2 float32 = 88 B a row, read
    # and written.
    assert REF.weight_bytes(TINY) == 1848
    assert REF.state_bytes_per_row(TINY) == 3 * 88
    got = REF.decode_bytes(TINY, 3, 10)
    assert isinstance(got, int) and got == 1848 + 32 * (10 + 3) + 2 * 3 * 264
    assert REF.decode_bytes(TINY, 0, 0) == 1848


@pytest.mark.parametrize("cfg", [TINY, harness.config("zamba2-7b")],
                         ids=["tiny", "published"])
def test_state_bytes_per_row_is_what_a_slot_holds(cfg):
    from repro.models import build_model
    from repro.models.params import map_tree

    model = build_model(REF.model_config(cfg))
    defs = model.paged_cache_defs(batch=1, max_len=64, n_pages=5,
                                  page_len=16)
    held = []
    map_tree(lambda d: held.append(math.prod(d.shape)
                                   * np.dtype(d.dtype).itemsize)
             if d.axes[:2] == ("layers", "batch") else None, defs)
    assert sum(held) == REF.state_bytes_per_row(cfg)


def test_weight_bytes_are_the_served_tree():
    import jax

    from repro.models import build_model

    tree = build_model(REF.model_config(TINY)).abstract_params()
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree)
               ) == REF.weight_bytes(TINY)


def reduction(modules, t0=0, t1=100_000):
    r = T.Reduction.__new__(T.Reduction)
    r.ops = [[]]
    r.modules = [list(modules)]
    r.host = []
    r.t0, r.t1 = t0, t1
    return r


def roofline_ctx(red, config="zamba2-7b", **info):
    run = types.SimpleNamespace(cell={"config": config}, config=TINY)
    return harness.Context(trace=red, run=run,
                           peak={"hbm_bytes_per_s": 1e9}, info=info)


def test_hbm_roofline_reads_bytes_over_device_time():
    read = harness.metric_reader("decode_step.hbm_roofline").read
    calls = [T.Event("jit_decode_step", 0, 1000),
             T.Event("jit_decode_step", 2000, 3000),
             T.Event("jit_chunk_step", 4000, 9000)]
    red = reduction(calls)
    # Two ticks with 3 live rows and 10 keys in all: the weights twice.
    want = 100 * (3848 + 1848) / (2e-6 * 1e9)
    assert read(roofline_ctx(red, decode_rows=3, decode_ctx=10,
                             decode_ticks=2)) == pytest.approx(want)
    assert read(roofline_ctx(red)) is None                 # no ticks counted
    assert read(roofline_ctx(reduction([]), decode_rows=3, decode_ctx=10,
                             decode_ticks=2)) is None       # no program
    # A configuration whose reference counts no bytes reads nothing.
    assert read(roofline_ctx(red, "qwen2-0.5b", decode_rows=3,
                             decode_ctx=10, decode_ticks=2)) is None


def test_state_reset_mb_is_the_mean_per_traced_tick():
    read = harness.metric_reader("batcher.state_reset_mb").read
    ticks = [types.SimpleNamespace(tick=i, state_reset_bytes=n)
             for i, n in enumerate([3_000_000, 0, 0, 1_000_000], 1)]
    ctx = harness.Context(trace=None, run=None, peak={},
                          info={"tick_events": ticks})
    assert read(ctx) == pytest.approx(1.0)
    # A program without the counter, or no traced tick, reads nothing.
    old = [types.SimpleNamespace(tick=1, eager_updates=3)]
    assert read(harness.Context(trace=None, run=None, peak={},
                                info={"tick_events": old})) is None
    assert read(harness.Context(trace=None, run=None, peak={},
                                info={})) is None
