"""Trace reduction on a small hand-built trace in the profiler's XSpace
shape: busy and idle time, kernel and program time, idle gaps named by the
harness spans, and the roofline and utilization arithmetic of the
per-layer readers."""
import json
from pathlib import Path

import pytest

import cells
import costs
import harness
import trace_reduce

REPO = Path(__file__).resolve().parents[2]
US = 1000


def _ev(name, start_us, end_us):
    return {"name": name, "start_ns": start_us * US,
            "duration_ns": (end_us - start_us) * US, "stats": {}}


def _space():
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        _ev("bench.window", 0, 1000), _ev("bench.submit", 0, 100),
        _ev("bench.drain", 100, 900), _ev("bench.idle", 900, 1000),
        _ev("PjitFunction(_raw_score)", 120, 140)]}]}
    ops = [_ev("fusion.1", 100, 300), _ev("join_flash_attention.2", 300, 500),
           _ev("fusion.3", 450, 600), _ev("copy.4", 700, 800),
           _ev("fusion.5", 990, 1100)]
    mods = [_ev("jit__raw_score(7)", 100, 600),
            _ev("jit_insert(9)", 700, 800), _ev("jit_insert(9)", 990, 1100)]
    dev0 = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": mods}]}
    dev1 = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [_ev("fusion.1", 0, 1000)]}]}
    return {"planes": [host, dev0, dev1]}


@pytest.fixture
def trace():
    return trace_reduce.reduce(json.loads(json.dumps(_space())), {0})


def test_window_busy_and_idle(trace):
    assert len(trace.devices) == 1
    assert trace.window_s == pytest.approx(1e-3)
    # union of [100, 600], [700, 800], [990, 1000] (clipped to the window)
    assert trace_reduce.busy_s(trace) == pytest.approx(610e-6)
    assert trace_reduce.idle_share(trace) == pytest.approx(0.39)
    both = trace_reduce.reduce(_space())
    assert trace_reduce.busy_s(both) == pytest.approx((610e-6 + 1e-3) / 2)


def test_kernel_and_program_time(trace):
    assert trace_reduce.matched_s(trace, r"^join_flash_attention") == \
        pytest.approx(200e-6)
    assert trace_reduce.matched_s(trace, r"jit__(raw|pool)_score",
                                  line="modules") == pytest.approx(500e-6)
    assert trace_reduce.matched_s(trace, r"jit_insert", line="modules") == \
        pytest.approx(110e-6)


def test_top_ops_and_idle_gaps(trace):
    top = dict(trace_reduce.top_ops(trace))
    assert top["fusion"] == pytest.approx((200 + 150 + 10) * 1e-6)
    assert top["join_flash_attention"] == pytest.approx(200e-6)
    gaps = trace_reduce.idle_gaps(trace)
    # [800, 990] is named by the drain around its midpoint
    assert gaps[0] == ["bench.drain", pytest.approx(190e-6)]
    assert sorted(g[0] for g in gaps[1:]) == ["bench.drain", "bench.submit"]
    assert all(g[1] == pytest.approx(100e-6) for g in gaps[1:])


def test_no_window_span_is_an_error():
    space = _space()
    space["planes"][0]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError):
        trace_reduce.reduce(space)


def _ctx(trace, cfg):
    rec = harness.Record(0, 0.0, 2, 20, [380, 100], done_s=0.5)
    return harness.Context(cfg, {}, {}, [rec], trace,
                           costs.peaks("TPU v5 lite"))


def test_roofline_and_mfu_readers(trace):
    cfg = json.loads((REPO / "bench" / "configs" / "bert_base_l6_fp16.json")
                     .read_text())
    ctx = _ctx(trace, cfg)
    w = [costs.join_attention_work(cfg, 20, ld) for ld in (380, 100)]
    least = 0.0
    for part in ("full", "cls"):
        ops = sum(x[part][0] for x in w)
        nbytes = sum(x[part][1] for x in w)
        least += max(ops / 197e12, nbytes / 819e9)
    roof = cells.reader(REPO, "join_attention_roofline")(ctx)
    assert roof == pytest.approx(100 * least / 200e-6)
    flops = sum(costs.score_step_flops(cfg, 20, ld) for ld in (380, 100))
    mfu = cells.reader(REPO, "score_step_mfu")(ctx)
    assert mfu == pytest.approx(100 * flops / 500e-6 / 197e12)
    idle = cells.reader(REPO, "device_idle_share")(ctx)
    assert idle == pytest.approx(39.0)


def test_readers_stay_silent_without_a_trace(trace):
    cfg = json.loads((REPO / "bench" / "configs" / "bert_base_l6_fp16.json")
                     .read_text())
    ctx = _ctx(None, cfg)
    for name in ("join_attention_roofline", "score_step_mfu",
                 "device_idle_share"):
        assert cells.reader(REPO, name)(ctx) is None
    empty = trace_reduce.reduce({"planes": [_space()["planes"][0]]})
    for name in ("join_attention_roofline", "score_step_mfu"):
        assert cells.reader(REPO, name)(_ctx(empty, cfg)) is None


def test_recorded_chip_trace():
    """An 8 ms slice of a traced ``l6_docs.steady`` window (TPU v5 lite),
    in the shape ``trace_reduce.xspace_dict`` writes: one scoring program
    runs through it, and the dense join kernel is its largest op."""
    space = json.loads((Path(__file__).with_name("fixtures")
                        / "l6_trace_excerpt.json").read_text())
    tr = trace_reduce.reduce(space, {0})
    assert tr.window_s == pytest.approx(8e-3)
    assert trace_reduce.idle_share(tr) == pytest.approx(0.0, abs=1e-3)
    assert trace_reduce.matched_s(tr, r"jit__(raw|pool)_score",
                                  line="modules") == pytest.approx(8e-3)
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "roof", REPO / "bench" / "metrics" / "join_attention_roofline.py")
    roof = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roof)
    kernel = trace_reduce.matched_s(tr, roof.KERNELS)
    assert kernel == pytest.approx(0.003742051)
    assert trace_reduce.top_ops(tr, 1)[0] == ["join_flash_attention",
                                              pytest.approx(kernel)]
