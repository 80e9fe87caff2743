"""The reduction from a trace to device metrics, the op and byte
counts, and the peak table, against values worked out by hand."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from rtbench import device, flops, harness, trace_reduce

DATA = Path(__file__).resolve().parent / "data"

K = 'custom-call(s32[4]), custom_call_target="tpu_custom_call", x={}'


def _trace():
    ops = [("%while.1 = (s32[]) while(x)", 100, 50),
           ("%fusion.1 = bf16[4] fusion(a)", 110, 10),
           ("%closed_call.2 = bf16[4] " + K, 125, 20),
           ("%fusion.3 = bf16[8] fusion(b)", 200, 30),
           ("%closed_call.4 = bf16[8] " + K, 235, 10)]
    modules = [("jit_paged_decode_steps_fn(11)", 100, 50),
               ("jit_ragged_prefill_fn(22)", 200, 50)]
    host = [("rtbench:window", 90, 210), ("dispatch:ragged", 160, 35),
            ("serve loop", 0, 100000)]
    return trace_reduce.Trace(ops=ops, modules=modules, host=host)


def test_reduce_by_hand():
    r = trace_reduce.reduce(_trace(), (90, 300))
    assert r["window_s"] == pytest.approx(210e-9)
    # busy: [100,150] + [200,230] + [235,245]
    assert r["busy_s"] == pytest.approx(90e-9)
    assert r["module_s"] == pytest.approx(
        {"paged_decode_steps_fn": 50e-9, "ragged_prefill_fn": 50e-9})
    assert r["launches"] == {"paged_decode_steps_fn": 1,
                             "ragged_prefill_fn": 1}
    assert r["kernel_s"] == pytest.approx(
        {"paged_decode_steps_fn": 20e-9, "ragged_prefill_fn": 10e-9})
    ops = dict(r["device_ops"])
    # the loop's own time is what its nested operations leave
    assert ops["paged_decode_steps_fn:while.1"] == pytest.approx(20e-9)
    assert ops["paged_decode_steps_fn:closed_call.2 [kernel]"] == \
        pytest.approx(20e-9)
    assert ops["ragged_prefill_fn:fusion.3"] == pytest.approx(30e-9)
    # gaps: 90-100, 150-200, 230-235, 245-300, longest first; the
    # enclosing spans say nothing and the dispatch covers the 50 ns gap
    assert r["idle_gaps"] == [["no host span", pytest.approx(55e-9)],
                              ["dispatch:ragged", pytest.approx(50e-9)],
                              ["no host span", pytest.approx(10e-9)],
                              ["no host span", pytest.approx(5e-9)]]
    assert trace_reduce.host_window(_trace(), "rtbench:window") == (90, 300)


def test_window_clips_events():
    r = trace_reduce.reduce(_trace(), (120, 210))
    assert r["busy_s"] == pytest.approx(40e-9)     # [120,150] + [200,210]
    assert r["module_s"]["ragged_prefill_fn"] == pytest.approx(10e-9)


SHAPE = flops.Shape(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
                    d_ff=16, vocab=10)


def test_counts_by_hand():
    assert SHAPE.layer_weights == 8 * 4 * (2 * 2 + 2 * 1) + 2 * 8 * 16
    d = flops.decode_work(SHAPE, [(32, 3)])
    # two decode steps: 33 and 34 keys
    assert d.matmul_flops == 2 * (2 * 2 * 448 + 2 * 80)
    assert d.kernel_ops == 4 * 2 * 4 * 67 * 2
    assert d.kernel_bytes == 2 * 1 * 4 * 2 * 67 * 2 + 2 * 2 * 4 * 2 * 2 * 2
    p = flops.prefill_work(SHAPE, [(32, 16)])
    # positions 16..31 see 17..32 keys: 392 in all
    assert p.matmul_flops == 2 * (16 * 2 * 448 + 80)
    assert p.kernel_ops == 4 * 2 * 4 * 392 * 2
    assert p.kernel_bytes == 2 * 1 * 4 * 2 * 48 * 2 + 2 * 2 * 4 * 2 * 16 * 2
    assert flops.decode_work(SHAPE, [(32, 1)]).flops == 0


def test_roofline_and_mfu_by_hand():
    peak = device.peaks("TPU v5 lite")
    w = flops.Work(matmul_flops=0.25 * peak["flops_per_s"] * 0.01,
                   kernel_ops=0.25 * peak["flops_per_s"] * 0.01,
                   kernel_bytes=0.1 * peak["bytes_per_s"] * 0.01)
    least, bound = flops.roofline_s(w, peak["flops_per_s"],
                                    peak["bytes_per_s"])
    assert (least, bound) == (pytest.approx(0.0025), "compute")
    run = SimpleNamespace(
        device={"kind": "TPU v5 lite"}, work={"decode": w,
                                              "prefill": flops.Work()},
        trace_summary={"module_s": {"paged_decode_steps_fn": 0.01},
                       "kernel_s": {"paged_decode_steps_fn": 0.005}})
    assert device.mfu(run, "decode") == pytest.approx(50.0)
    assert device.roofline(run, "decode") == pytest.approx(50.0)
    assert device.mfu(run, "prefill") is None
    assert device.roofline(run, "prefill") is None
    run.trace_summary = None
    assert device.mfu(run, "decode") is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="not in peaks.json"):
        device.peaks("TPU v99")
    with pytest.raises(KeyError):
        device.peaks("source")


def test_recorded_trace():
    """``data/trace.json``: one ragged prefill, one argmax and three decode
    launches of a two-layer model at head size 128 on a v5e chip
    (``data/record_trace.py``).  The expected values were worked out
    apart from ``reduce``: busy time by a sweep over the 685 operations'
    start and end points, launch time by adding the launches' durations,
    kernel time by adding the ``tpu_custom_call`` operations that start
    inside each launch."""
    fx = json.loads((DATA / "trace.json").read_text())
    tr = trace_reduce.Trace(*(list(map(tuple, fx[k]))
                              for k in ("ops", "modules", "host")))
    r = trace_reduce.reduce(tr, tuple(fx["window"]))
    assert r["window_s"] == pytest.approx(9765978e-9)
    assert r["busy_s"] == pytest.approx(1118870e-9)
    assert r["module_s"] == pytest.approx({
        "ragged_prefill_fn": 90051e-9, "_argmax": 843e-9,
        "paged_decode_steps_fn": 1031654e-9})
    assert r["launches"] == {"ragged_prefill_fn": 1, "_argmax": 1,
                             "paged_decode_steps_fn": 3}
    assert r["kernel_s"] == pytest.approx({
        "ragged_prefill_fn": 11853e-9, "paged_decode_steps_fn": 846135e-9})
    gaps = [g for _, g in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10
    assert sum(gaps) <= r["window_s"] - r["busy_s"] + 1e-12
    assert all(name != "no host span" for name, _ in r["idle_gaps"][:3])
    assert len(r["device_ops"]) == 10

    run = SimpleNamespace(trace_summary=r, device={"kind": fx["device_kind"]})
    idle = harness.reader("device.idle_share").read(run)
    assert idle == pytest.approx(100 * (1 - 1118870 / 9765978))
    # a decode window whose useful FLOPs would take 1% of its launches'
    # time at the peak, and whose kernel bytes half of the kernel's time
    peak = device.peaks(fx["device_kind"])
    run.work = {"decode": flops.Work(
        matmul_flops=0.01 * peak["flops_per_s"] * 1031654e-9,
        kernel_bytes=0.5 * peak["bytes_per_s"] * 846135e-9,
        kernel_ops=1.0)}
    assert device.mfu(run, "decode") == pytest.approx(1.0, rel=1e-6)
    assert device.roofline(run, "decode") == pytest.approx(50.0)
