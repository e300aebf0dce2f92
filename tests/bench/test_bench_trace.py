"""The reduction from a trace to busy and idle time, program and kernel
time and the breakdown: on a hand-built trace whose answers are known,
and on a small trace recorded on a TPU v5e."""
from pathlib import Path

import pytest
from bench_testkit import ROOT  # noqa: F401

from bench import trace as tr

US = 1000.0       # trace times are in ns


def _hand_trace():
    # window 0..100 us.  Program "jit_step" runs 10-40 us: a while loop
    # (10-40) holding fusion.1 (12-20) and fusion.2 (25-35); program
    # "jit_aes" runs 60-70 us: its kernel 62-68.  An op before the window
    # is left out.
    ops = [("%while.2 = (s32[]) while(...)", 10 * US, 40 * US),
           ("%fusion.1 = bf16[8] fusion(...)", 12 * US, 20 * US),
           ("%fusion.2 = bf16[8] fusion(...)", 25 * US, 35 * US),
           ("%aes_ctr.1 = s32[16,8,128] custom-call(s32[1])", 62 * US, 68 * US),
           ("%copy.1 = s32[4] copy(...)", 60 * US, 61 * US),
           ("%early = s32[4] copy(...)", -20 * US, -10 * US)]
    modules = [("jit_step(123)", 10 * US, 40 * US), ("jit_aes(456)", 60 * US, 70 * US),
               ("jit_early(1)", -20 * US, -10 * US)]
    main = [("bench.window", 0.0, 100 * US),
            ("bench.serve.generate", 2 * US, 45 * US),
            ("PjitFunction(step)", 3 * US, 9 * US),
            ("np.asarray(jax.Array)", 41 * US, 44 * US),
            ("bench.aes.wait", 46 * US, 60 * US)]
    other = [("ReadSyncFlag", 0.0, 100 * US)]
    ops.sort(key=lambda e: (e[1], -e[2]))
    return tr.Trace({"/device:TPU:0": tr.DeviceLine(ops, sorted(modules, key=lambda m: m[1]))},
                    {"python#0": main, "futex#1": other}, (0.0, 100 * US))


def test_busy_idle_and_window():
    t = _hand_trace()
    assert t.window_s == pytest.approx(100e-6)
    # busy: 10-40, 60-61 and 62-68 -> 37 us
    assert tr.busy_s(t) == pytest.approx(37e-6)
    assert tr.idle_share(t) == pytest.approx(0.63)
    assert tr.idle_gaps(t, "/device:TPU:0") == [(0.0, 10 * US), (40 * US, 60 * US),
                                                 (61 * US, 62 * US), (68 * US, 100 * US)]


def test_merge_clips_and_joins():
    assert tr.merge([(5, 10), (0, 3), (8, 12), (20, 30)], 1, 25) == [(1, 3), (5, 12), (20, 25)]


def test_self_time_takes_nested_ops_out_of_their_parent():
    got = tr.op_self_times(_hand_trace(), "/device:TPU:0")
    assert got == pytest.approx({"jit_step:while.2": 12 * US, "jit_step:fusion.1": 8 * US,
                                 "jit_step:fusion.2": 10 * US, "jit_aes:aes_ctr.1": 6 * US,
                                 "jit_aes:copy.1": 1 * US})


def test_program_runs_and_kernel_time():
    t = _hand_trace()
    assert tr.module_runs(t, "jit_step") == [(10 * US, 40 * US)]
    assert tr.module_runs(t, "jit_early") == []
    seconds, n = tr.op_seconds(t, lambda h: " custom-call(" in h)
    assert (seconds, n) == (pytest.approx(6e-6), 1)


def test_gaps_are_labelled_by_what_the_window_thread_did():
    t = _hand_trace()
    gaps = tr.idle_gaps(t, "/device:TPU:0")
    assert tr.gap_labels(t, gaps) == ["bench.serve.generate > PjitFunction(step)",
                                      "bench.aes.wait", "bench.window", "bench.window"]


def test_breakdown_lists_ops_and_idle_by_label():
    b = tr.breakdown(_hand_trace())
    assert b["device_ops"][0] == ["jit_step:while.2", pytest.approx(12e-6)]
    assert len(b["device_ops"]) == 5
    assert dict((k, v) for k, v in b["idle_gaps"]) == pytest.approx(
        {"bench.serve.generate > PjitFunction(step)": 10e-6, "bench.aes.wait": 20e-6,
         "bench.window": 33e-6})
    assert b["idle_gaps"][0][0] == "bench.window"


def test_names():
    assert tr.short_op("%fusion.3 = bf16[2] fusion(x)") == "fusion.3"
    assert tr.short_module("jit__decode(7880959121110125288)") == "jit__decode"


RECORDED = Path(__file__).parent / "data" / "aes-window.xplane.pb"


def test_recorded_tpu_trace():
    """A trace of a short AES window recorded on one TPU v5e: the device
    plane and the window span are found, and the numbers are sane."""
    t = tr.load(RECORDED)
    assert list(t.devices) == ["/device:TPU:0"]
    assert 0 < tr.busy_s(t) < t.window_s
    seconds, n = tr.op_seconds(t, lambda h: tr.short_op(h).startswith("aes_ctr")
                               and " custom-call(" in h)
    assert n > 0 and 0 < seconds / n < 100e-6
    b = tr.breakdown(t)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(t.window_s - tr.busy_s(t))
