"""Shared fakes for the dispatch-concurrency test suite.

`LatencyScriptedPredictor` is a deterministic stand-in for a remote
backend: answers and modeled latencies are pure functions of the prompt
text, so results and accounting are bit-identical no matter which worker
thread dispatched a call or in which order batches finished.  Tests force
worst-case interleavings through the `gate` hook (barriers / events run
at the start of every dispatch) and observe scheduling through the
thread-safe `dispatch_log`.
"""
import json
import threading
import time
import zlib

from repro.core.executors import CallResult, Predictor


class LatencyScriptedPredictor(Predictor):
    """Deterministic fake backend with scripted latency and dispatch hooks.

    * `answer_fn(instruction, rows) -> List[dict]` supplies answers (same
      contract as a registered oracle) and must be pure;
    * modeled latency is keyed by the prompt text alone and is always an
      exact binary fraction (multiples of 1/64 s), so float sums of any
      subset are exact in ANY accumulation order — concurrent dispatch
      cannot perturb aggregated latency statistics even in the last bit;
    * `gate(predictor, prompts)` runs at the start of every dispatch —
      install a `threading.Barrier` to force two backends to be mid-flight
      simultaneously, or an `Event` wait to hold a flush open;
    * `sleep_per_call_s` adds real wall time per call (overlap tests);
    * `dispatch_log` records `(thread_name, batch_size)` per dispatch.
    """
    name = "scripted"

    def __init__(self, answer_fn, *, base_latency_s: float = 0.25,
                 latency_fn=None, max_concurrency: int = 8, gate=None,
                 sleep_per_call_s: float = 0.0):
        self.options = {}
        self.answer_fn = answer_fn
        self.base_latency_s = float(base_latency_s)
        self.latency_fn = latency_fn
        self.max_concurrency = int(max_concurrency)
        self.gate = gate
        self.sleep_per_call_s = float(sleep_per_call_s)
        self._log_lock = threading.Lock()
        self.dispatch_log = []

    def latency_for(self, prompt: str) -> float:
        if self.latency_fn is not None:
            return float(self.latency_fn(prompt))
        return self.base_latency_s + (zlib.crc32(prompt.encode()) % 8) / 64.0

    def complete(self, prompt, schema, num_rows, *, shared_prefix="",
                 rows=None, instruction=""):
        if self.sleep_per_call_s:
            time.sleep(self.sleep_per_call_s)
        answers = self.answer_fn(
            instruction, rows if rows else [{}] * max(1, num_rows))
        take = answers if num_rows == 0 else answers[:num_rows]
        objs = [{n: a.get(n) for n, _ in schema} for a in take]
        confs = [float(a.get("__confidence__", 1.0)) for a in take]
        while len(objs) < num_rows:
            objs.append({n: None for n, _ in schema})
            confs.append(0.0)
        text = json.dumps(objs[0] if num_rows == 1 else objs)
        return CallResult(text, max(1, len(shared_prefix + prompt) // 4),
                          max(1, len(text) // 4), self.latency_for(prompt),
                          self.sleep_per_call_s,
                          confidences=confs if num_rows > 0 else None)

    def complete_many(self, prompts, schema, num_rows_list, *,
                      shared_prefix="", rows_list=None, instruction=""):
        if self.gate is not None:
            self.gate(self, list(prompts))
        with self._log_lock:
            self.dispatch_log.append(
                (threading.current_thread().name, len(prompts)))
        rows_list = rows_list if rows_list is not None \
            else [None] * len(prompts)
        return [self.complete(p, schema, nr, shared_prefix=shared_prefix,
                              rows=r, instruction=instruction)
                for p, nr, r in zip(prompts, num_rows_list, rows_list)]


def drain_stream(stream):
    """Collect one QueryStream: returns (rows, ExecStats).  Rows come out
    in chunk order, so equal inputs must produce byte-equal lists."""
    rows = []
    for chunk in stream.chunks():
        rows.extend(chunk.rows())
    return rows, stream.stats


def stream_stats_dict(stats) -> dict:
    """ExecStats as a comparable dict: drop wall_s and queue_wait_s (real
    time, the honest nondeterminism) — everything else must match exactly
    across interleavings and worker counts."""
    import dataclasses as _dc
    d = _dc.asdict(stats)
    d.pop("wall_s")
    d.pop("queue_wait_s")
    return d


def run_sessions(db, queries, *, concurrent: bool, start_barrier=None):
    """Multi-session determinism harness: run one `db.stream` per entry of
    `queries` ([(tenant, sql), ...]) either serially (submission order) or
    on N threads released together (plus `start_barrier`, if given, as an
    extra alignment hook for worst-case interleavings).  Returns the
    per-query list of (rows, stats_dict) in QUERY order regardless of
    completion order — the serial and concurrent return values of
    identical workloads must compare equal."""
    outcomes = [None] * len(queries)

    def one(i, tenant, sql):
        rows, stats = drain_stream(db.stream(sql, tenant=tenant))
        outcomes[i] = (rows, stream_stats_dict(stats))

    if not concurrent:
        for i, (tenant, sql) in enumerate(queries):
            one(i, tenant, sql)
        return outcomes
    errors = []

    def runner(i, tenant, sql):
        try:
            if start_barrier is not None:
                start_barrier.wait(timeout=10)
            one(i, tenant, sql)
        except BaseException as e:      # surfaced to the caller
            errors.append(e)

    threads = [threading.Thread(target=runner, args=(i, t, q),
                                name=f"session-{i}")
               for i, (t, q) in enumerate(queries)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    if errors:
        raise errors[0]
    return outcomes


def register_scripted(db, model_name: str, predictor: Predictor) -> None:
    """Bind a (usually shared) predictor instance to a model name through
    the custom-executor registry, so scripted backends run the full SQL
    parse → optimize → physical-pipeline → service path."""
    key = f"exec_{model_name}"
    db.register_executor(key, lambda entry: predictor)
    db.sql(f"CREATE LLM MODEL {model_name} PATH 'custom:{key}' ON PROMPT")
