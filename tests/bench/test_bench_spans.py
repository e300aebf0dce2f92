"""The readers of the serving engine's spans (``repro.serve.*``) and the
shared clock of ``bench.spans``: on a hand-built trace whose answers are
known, on the same trace without the program's spans (a program that
opens none), and on small traces recorded on a TPU v5e."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest
from bench_testkit import ROOT

from bench import spans as sp
from bench import trace as tr

MS = 1e6          # trace times are in ns
READERS = ("ttft_ms.serve", "token_gap_ms.serve", "readback_idle.serve",
           "host_reads_per_step.serve")
DATA = Path(__file__).parent / "data"


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"test_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ms(a, b):
    return (a * MS, b * MS)


# The host's clock, window 0..200 ms.  generate A (10-100 ms): prefill
# 12-30, then sample and read-back, decode steps at 40-55 and 65-80, each
# followed by sample and read-back.  generate B (110-190 ms): prefill
# 111-120, read-back 121-125, one decode step 125-140, read-back 141-151.
# Each step is launched (``PJRT_LoadedExecutable_Execute`` inside
# ``PjitFunction``) 0.5 ms after it is called; each read-back reads two
# slots, and the first read launches a slice (another program).  The device
# runs each step from 0.5 ms after its launch, and the slice at 33-34 ms,
# but its clock reads 2 ms early.
STEPS = [("_prefill", 12, 30, 13, 29), ("_decode", 40, 55, 41, 54),
         ("_decode", 65, 80, 66, 79), ("_prefill", 111, 120, 112, 119),
         ("_decode", 125, 140, 126, 139)]
READBACKS = [(31, 40), (56, 65), (81, 92), (121, 125), (141, 151)]
DEVICE_EARLY = 2
OFFSET = 1.5          # the clock's 2 ms less the 0.5 ms from launch to run


def _serve_trace(program_spans=True, drop_run=False):
    main = [("bench.window",) + _ms(0, 200), ("bench.serve.generate",) + _ms(9, 101),
            ("bench.serve.generate",) + _ms(109, 191)]
    ops, modules = [], []
    for fn, s, e, rs, re in STEPS:
        main += [(f"PjitFunction({fn})",) + _ms(s, s + 1),
                 ("PJRT_LoadedExecutable_Execute linkage",) + _ms(s + 0.5, s + 0.5)]
        run = _ms(rs - DEVICE_EARLY, re - DEVICE_EARLY)
        modules.append((f"jit_{fn}(7)",) + run)
        ops.append((f"%fusion.{len(ops)} = bf16[8] fusion(...)",) + run)
    main += [("PjitFunction(dynamic_slice)",) + _ms(32, 32.2),
             ("PJRT_LoadedExecutable_Execute linkage",) + _ms(32.1, 32.1)]
    ops.append(("%slice.1 = s32[1] slice(...)",) + _ms(33 - DEVICE_EARLY, 34 - DEVICE_EARLY))
    if drop_run:
        modules.pop(2)
    if program_spans:
        main += [("repro.serve.generate",) + _ms(10, 100),
                 ("repro.serve.generate",) + _ms(110, 190)]
        for fn, s, e, _, _ in STEPS:
            name = "prefill" if fn == "_prefill" else "decode"
            main += [(f"repro.serve.{name}",) + _ms(s, e),
                     ("repro.serve.sample",) + _ms(e, e + 1)]
        main += [("repro.serve.readback",) + _ms(s, e) for s, e in READBACKS]
        main += [("repro.serve.host_read",) + _ms(s + k, s + k + 1)
                 for s, _ in READBACKS for k in (1, 3)]
    ops.sort(key=lambda e: (e[1], -e[2]))
    return tr.Trace({"/device:TPU:0": tr.DeviceLine(ops, sorted(modules, key=lambda m: m[1]))},
                    {"python3#0": sorted(main, key=lambda e: (e[1], -e[2])),
                     "main/1#1": [("PJRT_LoadedExecutable_Execute",) + _ms(41, 41.1)]},
                    _ms(0, 200))


def test_readers_on_a_hand_built_trace():
    obs = SimpleNamespace(trace=_serve_trace())
    # first read-backs end 30 ms (A) and 15 ms (B) into their calls
    assert _reader("ttft_ms.serve")(obs) == pytest.approx(22.5)
    # read-back ends 25 and 27 ms apart in A, 26 in B
    assert _reader("token_gap_ms.serve")(obs) == pytest.approx(26.0)
    assert _reader("host_reads_per_step.serve")(obs) == pytest.approx(2.0)
    # read-backs hold 43 ms; shifted by 1.5 ms, the device is busy in them
    # only for the slice (1 ms): 42 ms of the 200 ms window
    assert _reader("readback_idle.serve")(obs) == pytest.approx(21.0)


def test_offset_pairs_each_run_with_its_launch():
    t = _serve_trace()
    assert sp.launches(t, "jit__decode") == [40.5 * MS, 65.5 * MS, 125.5 * MS]
    assert sp.launches(t, "jit__prefill") == [12.5 * MS, 111.5 * MS]
    assert sp.device_offset_ns(t, "jit__decode") == pytest.approx(OFFSET * MS)
    assert sp.device_offset_ns(t, "jit__prefill") == pytest.approx(OFFSET * MS)
    # without the shift, each decode run reaches 1 ms back into the read-back
    # before it, which hides 3 ms of idle time: 39 ms, not 42
    readbacks = [_ms(s, e) for s, e in READBACKS]
    assert sp.idle_in_ns(t, readbacks, 0.0) == pytest.approx(39 * MS)
    assert sp.idle_in_ns(t, readbacks, OFFSET * MS) == pytest.approx(42 * MS)


def test_offset_is_the_least_shift_past_every_launch():
    t = _serve_trace()
    # one step launched 3 ms late of the rest: the offset grows to fit it
    modules = t.devices["/device:TPU:0"].modules
    i = next(k for k, m in enumerate(modules) if m[0].startswith("jit__decode"))
    name, s, e = modules[i]
    modules[i] = (name, s - 3 * MS, e - 3 * MS)
    assert sp.device_offset_ns(t, "jit__decode") == pytest.approx((OFFSET + 3) * MS)


def test_no_offset_where_launches_and_runs_do_not_pair():
    t = _serve_trace(drop_run=True)
    assert sp.device_offset_ns(t, "jit__decode") is None
    assert sp.device_offset_ns(t, "jit__prefill") == pytest.approx(OFFSET * MS)
    assert _reader("readback_idle.serve")(SimpleNamespace(trace=t)) is None
    assert sp.device_offset_ns(_serve_trace(), "jit_other") is None


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_without_the_spans(name):
    assert _reader(name)(SimpleNamespace(trace=_serve_trace(False))) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_without_a_trace(name):
    assert _reader(name)(SimpleNamespace(trace=None)) is None
    assert _reader(name)(SimpleNamespace()) is None


def test_spans_in_the_window_overlap_and_count():
    assert sp.overlap_ns([(0, 10), (20, 30)], [(5, 25), (28, 40)]) == 12
    assert sp.overlap_ns([], [(0, 1)]) == 0
    t = _serve_trace()
    assert list(sp.by_thread(t, "repro.serve.decode")) == ["python3#0"]
    assert sp.count(t, "repro.serve.readback") == 5
    assert sp.count(t, "repro.serve.host_read") == 10
    assert sp.by_thread(t, "repro.none") == {}
    decode = sp.by_thread(t, "repro.serve.decode")["python3#0"]
    assert sp.inside(_ms(10, 100), decode) == [_ms(40, 55), _ms(65, 80)]
    t.window = _ms(50, 200)
    assert sp.count(t, "repro.serve.decode") == 2


def test_recorded_aes_trace_offset():
    """The AES program's 45 runs in a trace recorded on a TPU v5e pair with the
    dispatcher's 45 launches; the device's clock reads about 1 ms early."""
    t = tr.load(DATA / "aes-window.xplane.pb")
    assert len(sp.launches(t, "jit_aes_ctr")) == 45
    offset = sp.device_offset_ns(t, "jit_aes_ctr")
    assert 0 < offset < 2 * MS
    runs = sorted(s for _, s, _ in t.devices["/device:TPU:0"].modules)
    lag = [r + offset - h for h, r in zip(sp.launches(t, "jit_aes_ctr"), runs)]
    assert min(lag) == 0 and max(lag) < 1 * MS


@pytest.fixture(scope="module")
def recorded_serve():
    """Two ``generate`` calls of a small float32 qwen3 (4 slots, 8-token
    prompts, 4 new tokens each) recorded on a TPU v5e, under the
    benchmark's ``bench.window`` and ``bench.serve.generate`` spans."""
    return tr.load(DATA / "serve-window.xplane.pb")


def test_recorded_serve_trace_spans(recorded_serve):
    t = recorded_serve
    counts = {n: sp.count(t, f"repro.serve.{n}")
              for n in ("generate", "prefill", "decode", "sample", "readback", "host_read")}
    assert counts == {"generate": 2, "prefill": 2, "decode": 6, "sample": 8, "readback": 8,
                      "host_read": 32}
    (thread,) = sp.by_thread(t, "repro.serve.generate")
    calls = sp.by_thread(t, "repro.serve.generate")[thread]
    for name in ("prefill", "decode", "sample", "readback"):
        spans = sp.by_thread(t, f"repro.serve.{name}")[thread]
        assert sum(len(sp.inside(c, spans)) for c in calls) == counts[name]
        assert all(any(a <= s and e <= b for a, b in calls) for s, e in spans)
    for s, e in sp.by_thread(t, "repro.serve.readback")[thread]:
        assert len(sp.inside((s, e), sp.by_thread(t, "repro.serve.host_read")[thread])) == 4


def test_recorded_serve_trace_offset(recorded_serve):
    t = recorded_serve
    for program, runs in (("jit__decode", 6), ("jit__prefill", 2)):
        host = sp.launches(t, program)
        assert len(host) == runs
        offset = sp.device_offset_ns(t, program)
        assert 0 < offset < 2 * MS
        starts = sorted(s for n, s, _ in t.devices["/device:TPU:0"].modules
                        if tr.short_module(n) == program)
        lag = [r + offset - h for h, r in zip(host, starts)]
        assert min(lag) == 0 and max(lag) < 1 * MS


def test_recorded_serve_trace_unpaired_runs_give_no_offset(recorded_serve):
    t = recorded_serve
    line = t.devices["/device:TPU:0"]
    kept = line.modules
    first = next(m for m in kept if tr.short_module(m[0]) == "jit__decode")
    line.modules = [m for m in kept if m is not first]
    try:
        assert sp.device_offset_ns(t, "jit__decode") is None
        assert _reader("readback_idle.serve")(SimpleNamespace(trace=t)) is None
    finally:
        line.modules = kept


def test_recorded_serve_trace_readers(recorded_serve):
    t = recorded_serve
    obs = SimpleNamespace(trace=t)
    read = {name: _reader(name)(obs) for name in READERS}
    assert read["host_reads_per_step.serve"] == 4.0
    (calls,) = sp.by_thread(t, "repro.serve.generate").values()
    assert 0 < read["ttft_ms.serve"] < min(e - s for s, e in calls) / MS
    assert 0 < read["token_gap_ms.serve"] < t.window_s * 1e3
    readbacks = [s for v in sp.by_thread(t, "repro.serve.readback").values() for s in v]
    held = sum(e - s for s, e in readbacks) / (t.window[1] - t.window[0])
    assert 0 < read["readback_idle.serve"] <= 100 * held
