"""The serve loop's spans in a trace: decode launches paired with their
readbacks, the idle time no host phase covers, and the readers of the
four serve-loop metrics, against values worked out by hand."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from rtbench import harness, serve_spans, trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def _trace():
    # device: decode launches D- (before the window), D0 (clipped by
    # it), D1, D2, and one ragged prefill; each op runs as its launch
    launches = [("jit_paged_decode_steps_fn(1)", 10, 20),
                ("jit_paged_decode_steps_fn(1)", 100, 50),
                ("jit_ragged_prefill_fn(2)", 200, 20),
                ("jit_paged_decode_steps_fn(1)", 300, 50),
                ("jit_paged_decode_steps_fn(1)", 500, 40)]
    ops = [("%fusion.1 = bf16[4] fusion(a)", s, d) for _, s, d in launches]
    host = [("rtbench:window", 120, 480),
            ("readback:decode", 0, 5),       # its launch is not traced
            ("readback:decode", 15, 25),     # D-: ends 40
            ("readback:decode", 110, 45),    # D0: ends 155
            ("serve:wait", 150, 10),
            ("serve:advance", 160, 30),
            ("readback:prefill", 205, 20),
            ("serve:pack", 230, 50),
            ("readback:decode", 305, 52),    # D1: ends 357
            ("readback:decode", 505, 45),    # D2: ends 550
            ("dispatch:ragged", 560, 20)]    # not a serve-loop phase
    return trace_reduce.Trace(ops=ops, modules=launches, host=host)


def test_decode_launches_pair_with_their_readbacks_in_order():
    tr = _trace()
    # D- pairs with its readback but lies outside the window; D0 is
    # clipped by the window's start and kept, its lag from its own end
    assert serve_spans.decode_lags(tr, (120, 600)) == [5, 7, 10]
    assert serve_spans.decode_lags(tr, (0, 600)) == [10, 5, 7, 10]
    # a readback missing at the end leaves its launch unpaired
    tr.host = [h for h in tr.host if h[1] != 505]
    assert serve_spans.decode_lags(tr, (120, 600)) == [5, 7]


def test_idle_time_under_no_serve_loop_span():
    tr = _trace()
    # idle in (120, 600): [150,200] [220,300] [350,500] [540,600], 340 ns;
    # covered: 40 (readback to 155, wait, advance to 190), 55 (readback
    # to 225, pack), 7 (readback to 357), 10 (readback to 550)
    assert serve_spans.uncovered_idle(tr, (120, 600)) == \
        pytest.approx(1 - 112 / 340)
    assert serve_spans.uncovered_idle(tr, (300, 350)) is None
    s = serve_spans.summarize(tr, (120, 600))
    assert s["decode_lags_s"] == pytest.approx([5e-9, 7e-9, 10e-9])
    assert s["decode_launches"] == 3
    assert s["uncovered_idle_share"] == pytest.approx(228 / 340)


def _run(**kw):
    res = {"host_phase_s": {"predict": 0.5, "setup": 2.0, "admit": 0.1,
                            "pack": 0.05, "launch": 0.2, "tables": 0.03,
                            "wait": 5.0, "advance": 0.02},
           "n_tasks": 4, "decode_steps_executed": 80,
           "decode_dispatches": 40}
    run = dict(res=res, trace_summary={"window_s": 1.0},
               spans={"decode_lags_s": [0.002, 0.004]})
    run.update(kw)
    return SimpleNamespace(**run)


READERS = ("serve.host_ms_per_step", "sched.predict_ms_per_req",
           "serve.readback_lag_ms_per_step", "serve.readback_lag_max_s")


def test_readers_on_a_hand_made_run():
    read = {m: harness.reader(m).read(_run()) for m in READERS}
    # admit + pack + launch + tables + advance = 0.4 s over 80 steps
    assert read["serve.host_ms_per_step"] == pytest.approx(5.0)
    assert read["sched.predict_ms_per_req"] == pytest.approx(125.0)
    # 3 ms a launch, two steps a launch
    assert read["serve.readback_lag_ms_per_step"] == pytest.approx(1.5)
    assert read["serve.readback_lag_max_s"] == pytest.approx(0.004)


@pytest.mark.parametrize("run", [
    # a program without the phases or the readback spans
    _run(res={"n_tasks": 4, "decode_steps_executed": 80,
              "decode_dispatches": 40, "scheduler_overhead_s": 1.0},
         spans={"decode_lags_s": [], "decode_launches": 40,
                "uncovered_idle_share": 1.0}),
    # an untraced run
    _run(res={"host_phase_s": {}, "n_tasks": 0,
              "decode_steps_executed": 0, "decode_dispatches": 0},
         trace_summary=None, spans=None),
], ids=["no-spans", "untraced"])
def test_readers_read_nothing_where_nothing_is_there(run):
    for m in READERS:
        assert harness.reader(m).read(run) is None, m


def test_a_runs_trace_is_read_once(monkeypatch, tmp_path):
    tr = _trace()
    loads, lines = [], []
    monkeypatch.setattr(trace_reduce, "find", lambda d: d)
    monkeypatch.setattr(trace_reduce, "load",
                        lambda p: loads.append(p) or tr)
    run = _run(spans=None, out_dir=tmp_path, log=lines.append)
    run.res["aot_misses"] = 3
    assert harness.reader("serve.readback_lag_max_s").read(run) == \
        pytest.approx(10e-9)
    assert harness.reader("serve.readback_lag_ms_per_step").read(run) == \
        pytest.approx(1e-6 * 22 / 3 / 2)
    assert loads == [str(tmp_path / "trace")]
    assert len(lines) == 1 and lines[0].startswith("spans: 3 decode")
    assert "67.06%" in lines[0] and "aot_misses 3" in lines[0]


def _brute_uncovered(fx) -> float:
    """The uncovered share by a sweep over every event boundary: each
    piece between two boundaries is idle when no operation runs in it,
    and covered when a serve-loop span holds it."""
    lo, hi = fx["window"]
    ops = [(s, s + d) for _, s, d in fx["ops"]]
    cover = [(s, s + d) for n, s, d in fx["host"]
             if n.startswith(("serve:", "readback:"))]
    points = sorted({lo, hi} | {min(max(p, lo), hi)
                                for s, e in ops + cover for p in (s, e)})
    idle = uncovered = 0.0
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e in ops):
            continue
        idle += b - a
        if not any(s <= mid < e for s, e in cover):
            uncovered += b - a
    return uncovered / idle


def test_recorded_trace_with_serve_spans():
    """``data/trace_spans.json``: one ragged prefill, one argmax and four
    decode launches of ``record_trace.py``'s two-layer model on a v5e
    chip, with the serve loop's spans (``data/record_trace_spans.py``).
    The lags were read off the events: each decode module's end and the
    end of the next ``readback:decode`` span; the fourth launch's
    readback ends after the cut."""
    fx = json.loads((DATA / "trace_spans.json").read_text())
    tr = trace_reduce.Trace(*(list(map(tuple, fx[k]))
                              for k in ("ops", "modules", "host")))
    window = tuple(fx["window"])
    s = serve_spans.summarize(tr, window)
    assert s["decode_launches"] == 4
    assert s["decode_lags_s"] == pytest.approx(
        [(6187812 - 3711225) * 1e-9, (8996832 - 6627435) * 1e-9,
         (11722262 - 9487702) * 1e-9])
    assert s["uncovered_idle_share"] == pytest.approx(_brute_uncovered(fx))
    assert s["uncovered_idle_share"] < 0.01
    r = trace_reduce.reduce(tr, window)
    assert r["launches"] == {"ragged_prefill_fn": 1, "_argmax": 1,
                             "paged_decode_steps_fn": 4}
    # the longest idle gaps fall under the serve loop's own spans
    assert [n.split(":")[0] for n, _ in r["idle_gaps"][:4]] == \
        ["serve", "readback", "readback", "readback"]
    # the kernels' device ops carry the names given to pallas_call
    ops = dict(r["device_ops"])
    assert "paged_decode_steps_fn:paged_decode_kernel.12 [kernel]" in ops
    kernels = {trace_reduce.op_label(op[0]) for op in fx["ops"]
               if trace_reduce.KERNEL_MARK in op[0]}
    assert kernels == {"paged_decode_kernel.12 [kernel]",
                       "ragged_prefill_kernel.7 [kernel]"}
