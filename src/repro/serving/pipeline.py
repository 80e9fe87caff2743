"""Async host pipeline: the detokenize/bookkeeping completion worker.

The continuous-decode loops used to serialize host scheduling with
device compute — dispatch one decode step, ``block_until_ready`` on the
scheduler thread, read back, and only then schedule the next iteration.
``CompletionWorker`` moves the blocking readback (device sync + the
device→host copy, i.e. the "detokenize" stage of a production server)
onto a daemon thread fed by a submit queue, so the scheduler thread is
free while the device works; combined with the N-step decode windows
(``model.decode_steps*``) this is the engine's async host pipeline.

Determinism contract: the worker performs NO scheduling — it only
syncs and converts arrays.  Results are collected strictly FIFO, and
the serve loops consume a window's completion BEFORE making any
eviction/admission decision that depends on it ("in arrears"
bookkeeping), so completion order, admission decisions and every parity
counter are identical to the synchronous loop — the engine-vs-sim
parity tests pin this down at N ∈ {1, 2, 4}.

The one pipelining the worker deliberately does NOT do is speculative
next-window dispatch before the previous window's readback: that would
stretch the eviction lag from N-1 to 2N-1 steps and break the N=1
bit-parity default, for a latency win the multi-step window already
captures.

``host_phase`` times the serve loop's host phases on the profiler's
clock: each runs under a ``serve:<phase>`` span and adds its wall
seconds to the serve's ``host_phase_s``; the worker's readbacks run
under ``readback:<kind>``.  With both on one trace, each device idle
gap falls under the host work that held the chip back.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

import jax


@contextlib.contextmanager
def host_phase(phases: Dict[str, float], name: str) -> Iterator[None]:
    """Run the block as host phase ``name`` of a serve: under a
    ``serve:<name>`` profiler span (a no-op with no profiler attached),
    adding its ``perf_counter`` seconds to ``phases[name]``."""
    with jax.profiler.TraceAnnotation("serve:" + name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            phases[name] = (phases.get(name, 0.0)
                            + time.perf_counter() - t0)


class CompletionWorker:
    """Daemon thread draining device completions off the serve loop.

    ``submit(arrays, t0, kind=...)`` enqueues an in-flight device result
    with its launch timestamp; the worker blocks until the arrays are
    ready, converts them to host numpy under a ``readback:<kind>``
    profiler span, and queues ``(host, dt)`` where ``dt`` is the
    launch→ready wall-clock delta (what the serve loop charges to its
    virtual clock).  ``collect()`` returns results strictly in
    submission order; worker-side exceptions re-raise there, so device
    failures surface on the scheduler thread at the consume point.
    """

    def __init__(self, name: str = "completion-worker"):
        self._in: "queue.Queue" = queue.Queue()
        self._out: "queue.Queue" = queue.Queue()
        self._closed = False
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        self._thread.start()

    # -- worker side ---------------------------------------------------
    def _run(self) -> None:
        while True:
            item = self._in.get()
            if item is None:                   # close() sentinel
                return
            arrays, t0, span = item
            try:
                with jax.profiler.TraceAnnotation(span):
                    host = jax.tree.map(
                        lambda a: np.asarray(jax.block_until_ready(a)),
                        arrays)
                self._out.put((host, time.perf_counter() - t0, None))
            except BaseException as exc:       # re-raised at collect()
                self._out.put((None, time.perf_counter() - t0, exc))

    # -- scheduler side ------------------------------------------------
    def submit(self, arrays, t0: float, *,
               kind: Optional[str] = None) -> None:
        """Hand an in-flight device result (array or pytree) plus its
        launch timestamp to the worker; ``kind`` (``decode``,
        ``prefill``) names its readback span."""
        self._in.put((arrays, t0,
                      f"readback:{kind}" if kind else "readback"))

    def collect(self) -> Tuple[object, float]:
        """Block for the OLDEST submitted result; returns (host, dt).
        Raises whatever the readback raised on the worker thread."""
        host, dt, exc = self._out.get()
        if exc is not None:
            raise exc
        return host, dt

    def close(self, timeout: Optional[float] = 5.0) -> None:
        """Stop the worker and join its thread.  Idempotent — the
        serve() teardown path may reach an already-closed worker when
        an engine exception unwinds mid-window."""
        if self._closed:
            return
        self._closed = True
        self._in.put(None)
        self._thread.join(timeout=timeout)

    def __enter__(self) -> "CompletionWorker":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
