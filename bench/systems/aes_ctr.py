"""AES-128-CTR invocations under an open-loop schedule: the paper's
function body (vSwarm ``aes``) as a deployed instance runs it.

An invocation takes the payload as host bytes, sends it to the device
through ``repro.kernels.ops.aes_ctr(..., backend="pallas")`` and hands the
ciphertext back as host bytes.  The function's key is its configuration
and lives on the device; each invocation gets counter blocks of its own,
so no keystream block is used twice under the key.

One dispatcher (the main thread) issues each invocation at its due time,
in order, without waiting for earlier ones; one collector thread waits for
each result in order and stamps its completion.  Latency runs from the due
time to the result on the host, so a late dispatcher shows in it.
"""
from __future__ import annotations

import dataclasses
import queue
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import List, Optional

import numpy as np

from bench import device, stats, traffic
from bench.reference import aes as ref
from bench.result import Compared, Outcome

LEAD_S = 0.01           # from the end of set-up to the first due time
GRACE_S = 60.0          # how long past the window's close an answer is waited for
WARMUP_CALLS = 16


@dataclasses.dataclass
class AesObservation:
    dispatch_s: List[float]          # host clock, call to return, every invocation
    traced_invocations: int          # invocations dispatched in the traced window
    trace: object = None


class AesFunction:
    """One deployed instance: its key on the device, bytes in, bytes out."""

    def __init__(self, key: bytes, payload_bytes: int):
        import jax
        from repro.kernels import ops
        self._ops = ops
        self.payload_bytes = payload_bytes
        self.blocks = -(-payload_bytes // 16)
        self.key = jax.device_put(np.frombuffer(key, np.uint8).astype(np.int32))

    def call(self, payload: bytes, first_counter: int):
        """Enqueue the body on the device; returns the device array."""
        buf = np.zeros(self.blocks * 16, np.int32)
        buf[:len(payload)] = np.frombuffer(payload, np.uint8)
        return self._ops.aes_ctr(buf.reshape(self.blocks, 16), self.key,
                                 nonce=first_counter, backend="pallas")

    def result(self, out) -> bytes:
        """Wait for the ciphertext and bring it back as host bytes."""
        return np.asarray(out).astype(np.uint8).reshape(-1)[:self.payload_bytes].tobytes()


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(left if left < 0.002 else left - 0.001)


@dataclasses.dataclass
class Window:
    """What one open-loop window of invocations produced."""
    t0: float                        # host clock of due time 0
    done: np.ndarray                 # host clock of each answer, nan if none came
    results: List[Optional[bytes]]
    issued: np.ndarray               # host clock at which each call was made
    dispatch_s: List[float]
    traced: int                      # invocations dispatched inside the traced span


def setup(cell, seed: int, seconds: float):
    """The cell's function instance, due times, payloads and counters, from the seed."""
    rng = np.random.default_rng([seed, 0xAE5])
    payload_bytes = int(cell.config["payload_bytes"])
    key = rng.integers(0, 256, 16, dtype=np.uint8).tobytes()
    due = traffic.arrival_times(cell.traffic["arrivals"], seconds, rng)
    data = rng.integers(0, 256, (len(due), payload_bytes), dtype=np.uint8)
    fn = AesFunction(key, payload_bytes)
    counters = np.arange(len(due), dtype=np.int64) * fn.blocks
    warm = rng.integers(0, 256, payload_bytes, dtype=np.uint8).tobytes()
    for i in range(WARMUP_CALLS):      # counters past every invocation's
        fn.result(fn.call(warm, (len(due) + i) * fn.blocks))
    return fn, key, due, data, counters


def open_loop(fn: AesFunction, due: np.ndarray, payloads: List[bytes], counters: np.ndarray,
              seconds: float, trace_seconds: float = 0.0, trace_dir: Optional[Path] = None
              ) -> Window:
    """Issue each invocation at its due time and collect every answer, up
    to ``GRACE_S`` past the window's close.  With ``trace_seconds`` the
    profiler records the first that many seconds, as the span ``bench.window``."""
    import jax
    n = len(due)
    results: List[Optional[bytes]] = [None] * n
    done = np.full(n, np.nan)
    work: "queue.SimpleQueue" = queue.SimpleQueue()
    tracing = trace_seconds > 0
    ann = jax.profiler.TraceAnnotation

    def collect():
        for _ in range(n):
            i, out = work.get()
            if tracing:
                with ann("bench.aes.collect"):
                    r = fn.result(out)
            else:
                r = fn.result(out)
            done[i] = time.perf_counter()
            results[i] = r

    collector = threading.Thread(target=collect, name="bench-aes-collector", daemon=True)
    if tracing:
        jax.profiler.start_trace(str(trace_dir), profiler_options=device.profile_options())
    collector.start()
    dispatch_s: List[float] = []
    issued = np.full(n, np.nan)
    t0 = time.perf_counter() + LEAD_S
    window = ann("bench.window") if tracing else None
    traced = 0
    if window is not None:
        _sleep_until(t0)
        window.__enter__()
    for i in range(n):
        t_due = t0 + due[i]
        if window is not None and t_due >= t0 + trace_seconds:
            _sleep_until(t0 + trace_seconds)
            window.__exit__(None, None, None)
            window = None
            jax.profiler.stop_trace()
        if window is not None:
            with ann("bench.aes.wait"):
                _sleep_until(t_due)
            ts = time.perf_counter()
            with ann("bench.aes.dispatch"):
                out = fn.call(payloads[i], int(counters[i]))
            traced += 1
        else:
            _sleep_until(t_due)
            ts = time.perf_counter()
            out = fn.call(payloads[i], int(counters[i]))
        issued[i] = ts
        dispatch_s.append(time.perf_counter() - ts)
        work.put((i, out))
    if window is not None:          # the traced span outlasted the arrivals
        _sleep_until(t0 + trace_seconds)
        window.__exit__(None, None, None)
        jax.profiler.stop_trace()
    close = t0 + seconds
    _sleep_until(close)
    collector.join(timeout=max(0.0, close + GRACE_S - time.perf_counter()))
    return Window(t0, done, results, issued, dispatch_s, traced)


def latencies_ms(w: Window, due: np.ndarray) -> np.ndarray:
    """From due time to the answer on the host, for every answered invocation."""
    answered = ~np.isnan(w.done)
    return 1e3 * (w.done[answered] - (w.t0 + due[answered]))


def run(cell, *, seed: int, seconds: float, trace_seconds: float, t_start: float,
        peaks: dict) -> Outcome:
    fn, key, due, data, counters = setup(cell, seed, seconds)
    payloads = [row.tobytes() for row in data]
    print(f"bench: function deployed and warmed up {time.perf_counter() - t_start:.2f} s "
          f"after start", file=sys.stderr)
    n = len(due)
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace_seconds > 0 else None
    setup_s = time.perf_counter() + LEAD_S - t_start
    with device.CompileCounter() as compiles:
        w = open_loop(fn, due, payloads, counters, seconds, trace_seconds, trace_dir)
    memory_peak = device.memory_peak_bytes()
    answered = int((~np.isnan(w.done)).sum())
    in_window = int((w.done <= w.t0 + seconds).sum())
    latency_ms = latencies_ms(w, due)
    print(f"bench: {n} invocations due, {answered} answered, {in_window} in the window; "
          f"dispatch median {1e6 * float(np.median(w.dispatch_s)):.1f} us, dispatcher late by p99 "
          f"{1e3 * float(np.percentile(w.issued - (w.t0 + due), 99)):.3f} ms; "
          f"{compiles.count} compilations in the window", file=sys.stderr)
    e2e = {"setup_s": setup_s,
           "p50_ms": stats.percentile(latency_ms, 50),
           "p95_ms": stats.percentile(latency_ms, 95),
           "rps": stats.rate(in_window, seconds)}
    compared = check(w.results, data, key, counters)
    trace = None
    if trace_dir is not None:
        from bench import trace as tr
        trace = tr.load(tr.find(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return Outcome(attempted=n, failed=n - answered, end_to_end=e2e, compared=compared,
                   memory_peak_bytes=memory_peak,
                   observation=AesObservation(w.dispatch_s, w.traced, trace))


def check(results: List[Optional[bytes]], data: np.ndarray, key: bytes,
          counters: np.ndarray) -> List[Compared]:
    """Every answer against the reference's ciphertext, byte for byte.  An
    answer that never came is counted apart; a wrong one, byte by byte."""
    got = [i for i, r in enumerate(results) if r is not None]
    want = ref.ctr_encrypt(data[got], key, counters[got]) if got else np.zeros((0, 0))
    wrong = 0
    for row, i in enumerate(got):
        wrong += int(np.count_nonzero(np.frombuffer(results[i], np.uint8) != want[row]))
    return [Compared("unanswered", float(len(results) - len(got)), 0.0),
            Compared("wrong_bytes", float(wrong), 0.0)]
