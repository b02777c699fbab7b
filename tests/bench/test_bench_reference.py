"""The benchmark's plain reference and weights against the program's own
float32 joint forward, at a small size on the CPU."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model
import system
from smallcells import SMALL

REPO = Path(__file__).resolve().parents[2]


def _cfg(base, split):
    cfg = json.loads((REPO / "bench" / "configs" / f"{base}.json")
                     .read_text())
    cfg.update(SMALL, split_layer=split, compute_dtype="float32")
    cfg["kernels"] = {"attn_impl": "plain", "compress_impl": "plain"}
    return cfg


@pytest.mark.parametrize("base,split", [("bert_base_l6_fp16", 2),
                                        ("bert_base_l11_int8kv", 3)])
def test_reference_is_the_programs_joint_forward(base, split):
    system.import_program(REPO)
    from repro.core.prettr import init_prettr, rank_forward

    cfg = _cfg(base, split)
    pcfg = system.program_config(cfg)
    w = model.make_weights(2**31 + 17, cfg)
    prog_w, _ = init_prettr(jax.random.PRNGKey(0), pcfg)
    del prog_w["backbone"]["lm_head"]          # the ranker never reads it
    assert jax.tree.structure(w) == jax.tree.structure(prog_w)
    assert jax.tree.map(lambda a, b: (a.shape, a.dtype), w, prog_w) == \
        jax.tree.map(lambda a, b: (b.shape, b.dtype), w, prog_w)

    rng = np.random.default_rng(0)
    query = rng.integers(4, 512, 5)
    docs = [rng.integers(4, 512, n) for n in (3, 20, 39, 45, 10)]
    tokens, segs, valid = model.pack_pairs(query, docs, 8, 40)
    fwd = jax.jit(lambda p, t, s, v: rank_forward(p, pcfg, t, s, v))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(fwd(w, tokens, segs, valid))
    got = model.score_pairs(w, cfg, query, docs, block=2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.std(got) > 0.05


def test_weights_follow_the_seed():
    cfg = _cfg("bert_base_l6_fp16", 2)
    a = model.make_weights(2**31 + 5, cfg)
    b = model.make_weights(2**31 + 5, cfg)
    c = model.make_weights(5, cfg)
    same = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, b)
    assert all(jax.tree.leaves(same))
    assert not jnp.array_equal(a["score_head"], c["score_head"])


def test_lower_precisions_move_the_scores():
    cfg = _cfg("bert_base_l11_int8kv", 3)
    w = model.make_weights(3, cfg)
    rng = np.random.default_rng(1)
    query = rng.integers(4, 512, 6)
    docs = [rng.integers(4, 512, n) for n in (12, 39, 25)]
    ref = model.score_pairs(w, cfg, query, docs)
    fp8 = model.score_pairs(w, cfg, query, docs,
                            mm_dtype=jnp.dtype("float8_e4m3fn"))
    int4 = model.score_pairs(w, cfg, query, docs, kv_bits=4)
    int8 = model.score_pairs(w, cfg, query, docs, kv_bits=8)
    gap = [float(np.max(np.abs(x - ref))) for x in (int8, int4, fp8)]
    assert 0 < gap[0] < gap[1] < gap[2]


def test_gap_ratios_leave_out_each_requests_shared_offset():
    """A shift that all of one request's candidates share is taken out; the
    unit is the stated-precision reference's own centred gap."""
    import harness

    rng = np.random.default_rng(7)
    ref = rng.normal(size=300)
    noise = rng.normal(scale=0.01, size=300)
    stated = ref + noise
    shift = np.repeat(rng.normal(scale=5.0, size=3), 100)
    g = harness.gaps(ref + 2 * noise + shift, ref, stated, [100] * 3)
    assert g["rms_gap_ratio"] == pytest.approx(2.0, rel=1e-9)
    assert g["max_gap_ratio"] > g["rms_gap_ratio"]
    assert harness.gaps(stated, ref, stated, [100] * 3)[
        "rms_gap_ratio"] == pytest.approx(1.0)
    wrong = ref + noise
    wrong[5] += 1.0                            # one answer altered
    assert harness.gaps(wrong, ref, stated, [100] * 3)["max_gap_ratio"] > 50
