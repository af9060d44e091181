"""Regression tests for the concurrency fixes that fell out of the
lockcheck self-application (PR: interprocedural concurrency analysis).

Each test pins one fix:

* ServingMetrics.estimated_ttft_ms snapshots the rolling step-time
  deque before iterating (the engine thread appends concurrently).
* The profiler counter-provider registry is lock-protected, and
  counters() invokes providers OUTSIDE the lock (re-entrant
  registration must not deadlock).
* LLMEngine's hung-step tag hand-off (monitor thread -> dispatch
  thread) is synchronized by _hung_lock.
* PreemptionMonitor's signal handler only sets the Event; the store
  broadcast is deferred to the next requested() poll and happens
  exactly once.
"""
import signal
import threading
import time

import pytest


# ---------------------------------------------------------------------------
# ServingMetrics rolling deque
# ---------------------------------------------------------------------------
class _EngineStub:
    """Just enough engine for ServingMetrics to weakref and register."""


def test_ttft_estimate_survives_concurrent_step_records():
    """estimated_ttft_ms iterates the step-time window while the engine
    thread appends to it; without the tuple() snapshot a bounded deque
    that rotates mid-sum raises 'deque mutated during iteration'."""
    from paddle_tpu.serving.metrics import ServingMetrics

    eng = _EngineStub()
    m = ServingMetrics(eng)
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            m.record_step("decode", 8, dt_s=0.01 + (i % 7) * 1e-4,
                          prompt_tokens=0, decode_rows=1)
            i += 1

    def reader():
        try:
            while not stop.is_set():
                m.estimated_ttft_ms(queue_depth=3)
        except RuntimeError as e:  # "deque mutated during iteration"
            errors.append(e)

    threads = [threading.Thread(target=writer, daemon=True),
               threading.Thread(target=reader, daemon=True)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    assert not errors
    assert m.estimated_ttft_ms(queue_depth=0) is not None


# ---------------------------------------------------------------------------
# profiler counter-provider registry
# ---------------------------------------------------------------------------
def test_counter_registry_survives_concurrent_mutation():
    """register/unregister arrive from arbitrary threads (weakref
    finalizers); counters() must not see the dict change size under
    its iteration."""
    from paddle_tpu import profiler

    stop = threading.Event()
    errors = []

    def churn(tag):
        i = 0
        while not stop.is_set():
            name = f"test/churn-{tag}-{i % 16}"
            profiler.register_counter_provider(name, lambda: 1.0)
            profiler.unregister_counter_provider(name)
            i += 1

    def read():
        try:
            while not stop.is_set():
                profiler.counters()
        except RuntimeError as e:  # "dictionary changed size ..."
            errors.append(e)

    threads = [threading.Thread(target=churn, args=(k,), daemon=True)
               for k in range(2)]
    threads.append(threading.Thread(target=read, daemon=True))
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    for k in range(2):
        for i in range(16):
            profiler.unregister_counter_provider(f"test/churn-{k}-{i}")
    assert not errors


def test_counter_provider_may_register_reentrantly():
    """counters() calls providers OUTSIDE the registry lock, so a
    provider that itself registers a counter (e.g. lazy init on first
    read) must not deadlock."""
    from paddle_tpu import profiler

    def chained():
        return 7.0

    def provider():
        profiler.register_counter_provider("test/chained", chained)
        return 1.0

    profiler.register_counter_provider("test/reentrant", provider)
    try:
        done = []

        def run():
            done.append(profiler.counters())

        t = threading.Thread(target=run, daemon=True)
        t.start()
        t.join(timeout=5)
        assert done, "counters() deadlocked on re-entrant registration"
        assert done[0]["test/reentrant"] == 1.0
        assert profiler.counters()["test/chained"] == 7.0
    finally:
        profiler.unregister_counter_provider("test/reentrant")
        profiler.unregister_counter_provider("test/chained")


def test_counter_dead_provider_dropped():
    from paddle_tpu import profiler

    profiler.register_counter_provider("test/dead", lambda: None)
    out = profiler.counters()
    assert "test/dead" not in out
    # dropped from the registry, not just skipped
    assert "test/dead" not in profiler.counters()


# ---------------------------------------------------------------------------
# engine hung-step tag hand-off
# ---------------------------------------------------------------------------
def test_hung_tag_write_synchronized_with_consumer():
    """_on_step_timeout (watchdog MONITOR thread) and the dispatch-side
    swap both take _hung_lock: while the consumer holds it, the monitor
    write must block rather than interleave."""
    from paddle_tpu.serving.engine import LLMEngine

    eng = object.__new__(LLMEngine)  # just the hand-off attrs
    eng._hung_lock = threading.Lock()
    eng._hung_tags = None

    wrote = threading.Event()

    def monitor():
        eng._on_step_timeout([("decode:b8", 0.1, 0.5)])
        wrote.set()

    with eng._hung_lock:  # consumer mid-swap
        t = threading.Thread(target=monitor, daemon=True)
        t.start()
        assert not wrote.wait(0.2), \
            "_on_step_timeout wrote _hung_tags without taking _hung_lock"
        assert eng._hung_tags is None
    t.join(timeout=5)
    assert wrote.is_set()
    assert eng._hung_tags == "decode:b8"


# ---------------------------------------------------------------------------
# PreemptionMonitor: flag-only handler, deferred single post
# ---------------------------------------------------------------------------
def _posts_counted(mon):
    """Wrap mon._post with a counter; returns the count list."""
    calls = []
    orig = mon._post

    def counted():
        calls.append(1)
        orig()

    mon._post = counted
    return calls


def test_signal_handler_defers_store_post(tmp_path):
    """SIGTERM sets the flag but posts NOTHING from handler context
    (store RPC at an arbitrary interruption point is async-signal
    unsafe); the next requested() poll broadcasts the notice exactly
    once, and peers then see it."""
    from paddle_tpu.distributed.store import FileStore
    from paddle_tpu.distributed.watchdog import PreemptionMonitor

    store = FileStore(str(tmp_path))
    a, b = PreemptionMonitor(), PreemptionMonitor()
    a._store = b._store = store
    b._read_baseline()
    posts = _posts_counted(a)
    a.install()
    try:
        signal.raise_signal(signal.SIGTERM)
        assert a._flag.is_set()
        assert posts == [], "handler posted to the store directly"
        b._last_poll = -1e9
        assert not b.requested()      # nothing broadcast yet
        assert a.requested()          # poll context: safe to post now
        assert len(posts) == 1
        assert a.requested()          # idempotent: one record total
        assert len(posts) == 1
        b._last_poll = -1e9
        assert b.requested()          # peer sees the deferred notice
    finally:
        a.uninstall()


def test_programmatic_request_posts_synchronously(tmp_path):
    """request() runs on an ordinary thread — it must post before
    returning (schedulers rely on peers seeing the notice immediately)
    and must not re-post on later polls."""
    from paddle_tpu.distributed.store import FileStore
    from paddle_tpu.distributed.watchdog import PreemptionMonitor

    store = FileStore(str(tmp_path))
    a, b = PreemptionMonitor(), PreemptionMonitor()
    a._store = b._store = store
    b._read_baseline()
    posts = _posts_counted(a)
    a.request()
    assert len(posts) == 1
    b._last_poll = -1e9
    assert b.requested()
    assert a.requested()
    assert len(posts) == 1


def test_remote_notice_is_not_echoed(tmp_path):
    """A rank that learns of preemption FROM the store must not post
    its own copy of the record back (echo storm across the gang)."""
    from paddle_tpu.distributed.store import FileStore
    from paddle_tpu.distributed.watchdog import PreemptionMonitor

    store = FileStore(str(tmp_path))
    a, b = PreemptionMonitor(), PreemptionMonitor()
    a._store = b._store = store
    b._read_baseline()
    posts = _posts_counted(b)
    a.request()
    b._last_poll = -1e9
    assert b.requested()
    assert b.requested()
    assert posts == []
