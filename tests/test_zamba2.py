"""The hybrid family against the plain Zamba2 reference of the benchmark
(``bench/configs/zamba2-7b.py``) on seeded random weights, at a tiny size
that keeps every mechanism: two shared blocks applied three times (block
0 again with another adapter), two B/C groups, rank-4 adapters, and an
attention head width (2 d / heads) that is not d / heads.

Both sides compute in float32.  The system runs the chunked SSD and the
reference a sequential scan, so they sum in different orders: they agree
to about 3e-6 of the largest logit.  ``TOL`` (5e-5 of the largest logit)
leaves over ten times that, and every control below -- the system with one
published mechanism changed -- lies farther off, the nearest (tanh GELU)
by 2e-4.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import blocks, build_model
from repro.models.config import ModelConfig
from repro.parallel import steps as steps_lib
from repro.serving import ContinuousBatcher, Request

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
TOL = 5e-5


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(BENCH / "configs" / "zamba2-7b.py", "zamba2_7b_reference")
CFG = dict(
    json.loads((BENCH / "configs" / "zamba2-7b.json").read_text()),
    hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
    attention_head_dim=16, attention_hidden_size=64, intermediate_size=64,
    ffn_hidden_size=64, vocab_size=64, n_mamba_heads=4, mamba_headdim=16,
    mamba_d_state=8, mamba_ngroups=2, adapter_rank=4, num_hidden_layers=7,
    hybrid_layer_ids=[2, 4, 6], torch_dtype="float32",
    # Weights of unit scale after the norms, so every activation is O(1)
    # and each mechanism moves the logits measurably.
    initializer_range=0.2)
CFG["layers_block_type"] = ["hybrid" if i in CFG["hybrid_layer_ids"]
                            else "mamba" for i in range(7)]


@pytest.fixture(scope="module")
def tiny():
    mc = REF.model_config(CFG)
    w = REF.init_weights(jax.random.PRNGKey(0), CFG, jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (12,), 1, 64)
    return mc, w, toks, REF.logits(w, toks, CFG)


def _gap(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _forward(mc, w, toks):
    return build_model(mc).forward(w, toks[None])[0][0]


def test_tiny_configuration_keeps_every_mechanism(tiny):
    mc, w, _, _ = tiny
    assert mc.hd == 16 != mc.d_model // mc.n_heads
    assert [k for k, _ in mc.stages()] == ["mamba", "shared_attn"] * 3 + [
        "mamba"]
    assert {k for k in w if k.startswith("shared_b")} == {"shared_b0",
                                                         "shared_b1"}
    assert w["s01_shared_attn"]["lora_b"].shape == (4, 128)
    assert w["s00_mamba"]["mamba"]["A_log"].dtype == jnp.float32
    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        build_model(mc).abstract_params())
    assert jax.tree.map(lambda a: (a.shape, a.dtype), w) == want


def test_forward_logits_match_the_reference(tiny):
    mc, w, toks, want = tiny
    assert _gap(_forward(mc, w, toks), want) < TOL


def _one_group(w):
    """Group 1's B and C made group 0's: what a single group computes."""
    g, n = CFG["mamba_ngroups"], CFG["mamba_d_state"]
    out = dict(w)
    for k, st in w.items():
        if k.endswith("_mamba"):
            m = dict(st["mamba"])
            for nm in ("wbc", "conv_bc", "conv_bc_b"):
                a = m[nm].reshape(m[nm].shape[:-1] + (2, g, n))
                a = a.at[..., 1, :].set(a[..., 0, :])
                m[nm] = a.reshape(m[nm].shape)
            out[k] = dict(st, mamba=m)
    return out


def _no_adapters(w):
    return {k: dict(v, lora_a=jnp.zeros_like(v["lora_a"]))
            if k.endswith("_shared_attn") else v for k, v in w.items()}


@pytest.mark.parametrize("control", ["adapters_dropped", "one_bc_group",
                                     "scale_hd", "tanh_gelu"])
def test_controls_fail(tiny, monkeypatch, control):
    """Each published mechanism changed alone moves the system's logits
    beyond the tolerance."""
    mc, w, toks, want = tiny
    if control == "adapters_dropped":
        w = _no_adapters(w)
    elif control == "one_bc_group":
        w = _one_group(w)
    elif control == "scale_hd":
        monkeypatch.setattr(blocks, "_score_denom",
                            lambda cfg, hd: jnp.asarray(hd, jnp.float32))
    else:
        mc = dataclasses.replace(mc, act="gelu")
    assert _gap(_forward(mc, w, toks), want) > TOL


def _served_gaps(batcher, reqs, w):
    """Per request: how far each served token's reference logit lies
    below the reference's best at its position (greedy: 0 when they
    agree), as a share of the largest logit."""
    got = batcher.run([Request(r.rid, list(r.prompt), r.max_new_tokens)
                       for r in reqs])
    gaps = []
    for r in reqs:
        served = got[r.rid]
        seq = jnp.asarray(r.prompt + served[:-1], jnp.int32)
        lg = REF.logits(w, seq, CFG)[len(r.prompt) - 1:]
        pick = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], 1)[:, 0]
        gaps.append(float(jnp.max(jnp.max(lg, 1) - pick)
                          / jnp.max(jnp.abs(lg))))
    return gaps


def _requests(n, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(1, 64, size=3 + 2 * i)
                    .tolist(), max_new_tokens=4 + i) for i in range(n)]


@pytest.mark.parametrize("prefill_chunk", [1, 4])
def test_paged_batcher_serves_the_reference(tiny, prefill_chunk):
    """Prompt then decode through the paged batcher (slot reuse resets the
    conv and SSM state; with chunks of 4, rows that finish their prompt
    mid-chunk are frozen for the rest of it) agrees with the reference's
    full forward on every served token."""
    mc, w, _, _ = tiny
    b = ContinuousBatcher(build_model(mc), w, slots=2, max_len=32,
                          kv_cache="paged", prefill_chunk=prefill_chunk)
    assert max(_served_gaps(b, _requests(5), w)) < TOL


def test_frozen_rows_hold_their_ssm_and_conv_state(tiny):
    """The chunk step leaves a row past its valid count exactly as if it
    had stepped only its valid tokens."""
    mc, w, _, _ = tiny
    model = build_model(mc)
    b = ContinuousBatcher(model, w, slots=2, max_len=32, kv_cache="paged")
    rows = b.padded_slots
    b.cache["pages"] = b.cache["pages"].at[:2, 0].set(jnp.asarray([1, 2]))
    chunk = jax.jit(steps_lib.make_chunk_step(model, b._batch_axes))
    toks = jnp.asarray(np.arange(1, 1 + 4 * rows).reshape(rows, 4) % 63 + 1,
                       jnp.int32)
    full = jnp.full((rows,), 4, jnp.int32).at[1].set(2)
    _, c4 = chunk(w, b.cache, toks, full)
    _, c2 = chunk(w, b.cache, toks[:, :2], jnp.full((rows,), 2, jnp.int32))
    for nm in (k for k in c4 if k.endswith("_mamba")):
        for leaf in ("conv_x", "conv_bc", "ssm"):
            np.testing.assert_array_equal(c4[nm][leaf][:, 1],
                                          c2[nm][leaf][:, 1])
            assert not np.array_equal(c4[nm][leaf][:, 0], c2[nm][leaf][:, 0])
    assert int(c4["idx"][1]) == 2 and int(c4["idx"][0]) == 4


def _stages_before(n: int, period: int) -> list[tuple[str, int]]:
    """The stages the period shorthand gave before the published layer."""
    out, remaining = [], n
    while remaining > 0:
        run = min(period, remaining)
        out.append(("mamba", run))
        remaining -= run
        if remaining > 0 or run == period:
            out.append(("shared_attn", 1))
    return out


@pytest.mark.parametrize("n,period", [(38, 6), (2, 2), (4, 2), (6, 6),
                                      (7, 3)])
def test_period_shorthand_keeps_its_stages(n, period):
    cfg = ModelConfig(name="t", family="hybrid", n_layers=n, d_model=32,
                      n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=64,
                      ssm_state=8, shared_attn_period=period)
    assert cfg.hybrid_ids == tuple(range(period, n + 1, period))
    assert cfg.stages() == _stages_before(n, period)


def _hf_model(w):
    """transformers' Zamba2ForCausalLM at the tiny size, holding ``w``."""
    torch = pytest.importorskip("torch")
    tz = pytest.importorskip("transformers.models.zamba2.modeling_zamba2")
    from transformers import Zamba2Config

    hc = Zamba2Config(
        vocab_size=64, hidden_size=32, num_hidden_layers=7,
        layers_block_type=CFG["layers_block_type"], mamba_d_state=8,
        mamba_d_conv=4, mamba_expand=2, mamba_ngroups=2, n_mamba_heads=4,
        intermediate_size=64, hidden_act="gelu", num_attention_heads=4,
        num_key_value_heads=4, num_mem_blocks=2, adapter_rank=4,
        use_shared_attention_adapter=False, use_mem_rope=True,
        rope_theta=10000, rms_norm_eps=1e-5, time_step_min=1e-3,
        tie_word_embeddings=True, max_position_embeddings=64,
        attn_implementation="eager")
    model = tz.Zamba2ForCausalLM(hc).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))  # noqa: E731
    stages = REF.stages(CFG)
    mixers = [(f"s{i:02d}_mamba", l) for i, (k, n) in enumerate(stages)
              if k == "mamba" for l in range(n)]
    apps = [f"s{i:02d}_shared_attn" for i, (k, _) in enumerate(stages)
            if k == "shared_attn"]
    with torch.no_grad():
        model.model.embed_tokens.weight.copy_(t(w["embed"]))
        model.model.final_layernorm.weight.copy_(t(w["final_norm"]["scale"]))
        for li, layer in enumerate(model.model.layers):
            nm, l = mixers[li]
            p = jax.tree.map(lambda a: a[l], w[nm])
            m = p["mamba"]
            dec = layer.mamba_decoder if hasattr(layer, "linear") else layer
            dec.input_layernorm.weight.copy_(t(p["ln1"]["scale"]))
            mx = dec.mamba
            mx.in_proj.weight.copy_(t(jnp.concatenate(
                [m["wz"], m["wx"], m["wbc"], m["wdt"]], 1).T))
            conv = jnp.concatenate([m["conv_x"], m["conv_bc"]], 1)
            mx.conv1d.weight.copy_(t(conv.T[:, None, :]))
            mx.conv1d.bias.copy_(t(jnp.concatenate([m["conv_x_b"],
                                                    m["conv_bc_b"]])))
            for k in ("A_log", "D", "dt_bias"):
                getattr(mx, k).copy_(t(m[k]))
            mx.norm.weight.copy_(t(m["gnorm"]))
            mx.out_proj.weight.copy_(t(m["wo"].T))
            if hasattr(layer, "linear"):
                a = CFG["hybrid_layer_ids"].index(li)
                app = w[apps[a]]
                blk = w[f"shared_b{a % 2}"]
                layer.linear.weight.copy_(t(app["linear"].T))
                st = layer.shared_transformer
                st.input_layernorm.weight.copy_(t(blk["ln1"]["scale"]))
                st.pre_ff_layernorm.weight.copy_(t(blk["ln2"]["scale"]))
                at = blk["attn"]
                for k, proj in (("wq", "q_proj"), ("wk", "k_proj"),
                                ("wv", "v_proj")):
                    getattr(st.self_attn, proj).weight.copy_(
                        t(at[k].reshape(at[k].shape[0], -1).T))
                st.self_attn.o_proj.weight.copy_(
                    t(at["wo"].reshape(-1, at["wo"].shape[-1]).T))
                ff = st.feed_forward
                ff.gate_up_proj.weight.copy_(t(jnp.concatenate(
                    [blk["mlp"]["wg"], blk["mlp"]["wi"]], 1).T))
                ff.down_proj.weight.copy_(t(blk["mlp"]["wo"].T))
                lora = ff.gate_up_proj_adapter_list[a]
                lora[0].weight.copy_(t(app["lora_a"].T))
                lora[1].weight.copy_(t(app["lora_b"].T))
    return model


def test_reference_matches_transformers_zamba2(tiny):
    """The reference is the published model: transformers' torch
    Zamba2ForCausalLM holding the same weights gives the same logits."""
    torch = pytest.importorskip("torch")
    _, w, toks, want = tiny
    model = _hf_model(w)
    with torch.no_grad():
        got = model(torch.tensor(np.asarray(toks))[None]).logits[0].numpy()
    assert _gap(jnp.asarray(got), want) < TOL
