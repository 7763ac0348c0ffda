"""One run of one benchmark cell: set-up, the measured window, the checks
that decide ``correct``, and the metrics.

The cell names a configuration (``bench/configs/<config>.json``), whose
``family`` key names its architecture's reference model and counts
(``bench/families/<family>.py``), and a traffic mix
(``bench/traffic/<mix>.json``); each metric is read by
``bench/metrics/<metric>.py`` and each cell's comparison limits are in
``bench/limits/<cell>.json``.  Nothing here is specific to one of them.

The window drives the user's entry points: ``IPDB.sql`` in a closed loop,
``IPDB.stream`` from client threads in an open loop.  A tap on the JAX
executor's dispatch (``JaxExecutor.complete_many``) records every prompt and
every answer the model served; the checks compare the SQL results with those
answers and, after the program's state is freed, a sample of the answers
with the family's plain reference model.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import resource
import shutil
import string
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import peaks, reference, trace_reduce, traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"

# ExecStats counters that must stay zero: any of them means a call failed
FAILURE_COUNTERS = ("retries", "batch_fallbacks", "transient_retries",
                    "deadline_drops", "backend_timeouts",
                    "breaker_rejections")
# the reference re-reads, per run, at least this many served tokens and
# served tokens that had a choice, from at most this many requests
SAMPLE_TOKENS = 300
SAMPLE_CHOICES = 64
SAMPLE_MAX_REQUESTS = 64
# an open-loop query is waited for this long past the window's close
OPEN_GRACE_S = 60.0
# where each platform's trace keeps its device operations (the CPU's serve
# the tests only: no CPU number is reported as a device metric)
TRACE_LINES = {
    "tpu": {},
    "cpu": {"device_prefix": "/host:CPU", "ops_line": "tf_XLAPjRtCpuClient",
            "modules_line": ""},
}


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# -- the cell -------------------------------------------------------------------
def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_module(name: str, path: Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, bench_dir: Path = BENCH) -> Callable:
    """``bench/metrics/<name>.py``'s ``read(ctx)``."""
    return _load_module(f"bench_metric_{name}",
                        bench_dir / "metrics" / f"{name}.py").read


def load_family(cfg: dict, bench_dir: Path = BENCH, where: str = ""):
    """``bench/families/<family>.py`` for the configuration's ``family``
    key: its ``KEYS``, reference model and ``Dims`` (the interface is in
    ``bench/families/dense.py``).  ``where`` names the configuration's file
    in the errors; there is no default family."""
    where = where or repr(cfg.get("name"))
    family = cfg.get("family")
    if not family:
        raise ValueError(f"configuration {where} has no 'family' key: it "
                         f"names its bench/families/<family>.py")
    path = bench_dir / "families" / f"{family}.py"
    if not path.is_file():
        raise FileNotFoundError(f"'family': {family!r} of configuration "
                                f"{where} names {path}, which is not there")
    return _load_module(f"bench_family_{family}", path)


def cell_plan(spec: dict, workload: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, found by the names in BENCHMARK.json:
    its configuration file and that file's family, its mix's data file, its
    metrics' readers and its limits, all under ``root``."""
    bench_dir = root / "bench"
    wl = {w["name"]: w for w in spec["workloads"]}.get(workload)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    with open(root / entry["file"]) as f:
        cfg = json.load(f)
    family = load_family(cfg, bench_dir, entry["file"])
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if workload in m.get("workloads", [workload])
             and m["moves"] in reported]
    with open(bench_dir / "limits" / f"{workload}.json") as f:
        limits = json.load(f)
    readers = {m["name"]: load_reader(m["name"], bench_dir)
               for m in e2e + layer}
    return {"workload": wl, "config": cfg, "family": family,
            "mix": traffic.load(wl["traffic"], bench_dir),
            "end_to_end": e2e, "per_layer": layer, "readers": readers,
            "limits": limits, "chips": int(wl["chips"])}


# -- the model ------------------------------------------------------------------
def model_seed(seed: int) -> int:
    """The weights' seed: the run's seed folded into JAX's 32-bit range."""
    return int(seed) % (2 ** 31 - 1)


def program_config(cfg: dict, smoke: bool):
    """The program's ModelConfig for a configuration file: its architecture
    as the ``jax:`` path serves it (or the tiny smoke variant in tests)."""
    import repro.configs as C
    arch = cfg["serve"]["arch"]
    if smoke:
        return C.get_smoke_config(arch).replace(vocab_size=259)
    return C.get_config(arch)


def reference_config(cfg: dict, smoke: bool, family) -> dict:
    """The sizes the reference runs at: the file's, or the smoke variant's
    (tests only: the family's ``KEYS``) with the file's published norm and
    rope settings."""
    if not smoke:
        return cfg
    pc = program_config(cfg, smoke)
    out = dict(cfg)
    out.update({k: getattr(pc, k) for k in family.KEYS})
    return out


def check_program_config(cfg: dict, family) -> None:
    """The program's configuration must be the one the file states: the
    family's ``KEYS`` and the norm, rope and dtype keys."""
    pc = program_config(cfg, smoke=False)
    bad = {k: (getattr(pc, k), cfg[k]) for k in family.KEYS
           if getattr(pc, k) != cfg[k]}
    for k in ("norm_type", "rope_theta", "param_dtype", "compute_dtype"):
        if getattr(pc, k) != cfg[k]:
            bad[k] = (getattr(pc, k), cfg[k])
    if bad:
        raise ValueError(f"program config differs from {cfg['name']}: {bad}")


def _options_sql(opts: dict) -> str:
    return "{ " + ", ".join(
        f"'{k}': " + (f"'{v}'" if isinstance(v, str) else str(v))
        for k, v in opts.items()) + " }"


def model_options(cfg: dict, mix: dict, seed: int, smoke: bool) -> dict:
    opts = dict(cfg["options"])
    opts.update(mix.get("model_options", {}))
    opts["smoke"] = 1 if smoke else 0
    opts["seed"] = model_seed(seed)
    return opts


# -- taps -----------------------------------------------------------------------
class Tap:
    """Records each dispatch the JAX executor serves: the prompts as the
    engine tokenizes them, the answers, and the logits (byte ids only) each
    served token was sampled from, in the order the engine sampled them.
    In a traced run it also opens the benchmark's host spans around the
    calls into the service and the engine."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.records: List[dict] = []
        self.recording = False
        self._lock = threading.Lock()
        self._local = threading.local()
        self._undo: List[tuple] = []

    def _wrap(self, owner, attr: str, make) -> None:
        orig = getattr(owner, attr, None)
        if orig is None:
            return
        self._undo.append((owner, attr, orig))
        setattr(owner, attr, make(orig))

    def install(self) -> None:
        from repro.core.executors import JaxExecutor
        from repro.serving.engine import InferenceEngine
        tap = self

        def dispatch(orig):
            def complete_many(ex, prompts, schema, num_rows_list, **kw):
                t0 = time.perf_counter()
                # grammar id -> (grammar, logits rows), in first-sampled
                # order, which is the order of the dispatched prompts
                tap._local.rows = {}
                with tap.span("dispatch"):
                    out = orig(ex, prompts, schema, num_rows_list, **kw)
                seen = list(tap._local.rows.values())
                tap._local.rows = None
                if tap.recording:
                    pre = kw.get("shared_prefix", "")
                    rec = {"t0": t0, "t1": time.perf_counter(),
                           "prompts": [pre + p for p in prompts],
                           "num_rows": list(num_rows_list),
                           "rows": list(kw.get("rows_list")
                                        or [None] * len(prompts)),
                           "schema": [tuple(f) for f in schema],
                           "max_str": int(ex.options.get("max_str", 24)),
                           "texts": [r.text for r in out],
                           "logits": [np.stack(r) for _, r in seen]
                           if len(seen) == len(prompts) else
                           [None] * len(prompts),
                           "radix_hit_tokens": sum(r.radix_hit_tokens
                                                   for r in out)}
                    with tap._lock:
                        tap.records.append(rec)
                return out
            return complete_many

        def sample(orig):
            def _sample(eng, logits, gs, states, temperature):
                rows = getattr(tap._local, "rows", None)
                if rows is not None:
                    for b, g in enumerate(gs):
                        if g is not None:
                            rows.setdefault(id(g), (g, []))[1].append(
                                np.array(logits[b, :reference.BYTE_IDS]))
                with tap.span("sample"):
                    return orig(eng, logits, gs, states, temperature)
            return _sample

        def spanned(name):
            def make(orig):
                def call(*a, **kw):
                    with tap.span(name):
                        return orig(*a, **kw)
                return call
            return make

        self._wrap(JaxExecutor, "complete_many", dispatch)
        self._wrap(InferenceEngine, "_sample", sample)
        if self.traced:
            self._wrap(InferenceEngine, "_prefill", spanned("prefill"))
            self._wrap(InferenceEngine, "paged_prefill", spanned("prefill"))

    def uninstall(self) -> None:
        for owner, attr, orig in reversed(self._undo):
            setattr(owner, attr, orig)
        self._undo.clear()

    def span(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name)


class CompileCounter:
    """Counts XLA backend compiles (a persistent-cache load counts too) and
    keeps the names of the programs."""

    def __init__(self):
        import jax
        self.names: List[str] = []
        self.active = True
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def count(self) -> int:
        return len(self.names)

    def _on(self, event: str, secs: float, fun_name: str = "?", **_) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.names.append(fun_name)


class HostWatch:
    """What the host did to the process during the window: its garbage
    collections (the longest pause, the full ones) and the times it was
    made to wait for a core (involuntary context switches)."""

    def __init__(self):
        self.pauses: List[tuple] = []           # (generation, seconds)
        self._t = 0.0

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __enter__(self) -> "HostWatch":
        self._ru = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on)
        ru = resource.getrusage(resource.RUSAGE_SELF)
        self.nivcsw = ru.ru_nivcsw - self._ru.ru_nivcsw
        self.cpu_s = (ru.ru_utime + ru.ru_stime
                      - self._ru.ru_utime - self._ru.ru_stime)

    def summary(self) -> dict:
        return {"gc_max_s": max((p for _, p in self.pauses), default=0.0),
                "gc_full": sum(1 for g, _ in self.pauses if g == 2),
                "nivcsw": self.nivcsw, "cpu_s": self.cpu_s}


# -- the run --------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    plan: dict
    db: object = None
    tap: Optional[Tap] = None
    text_ids: Dict[str, int] = dataclasses.field(default_factory=dict)
    slices: List[List[int]] = dataclasses.field(default_factory=list)
    warm_ids: List[int] = dataclasses.field(default_factory=list)
    schedule: Optional[dict] = None


def build(plan: dict, seed: int, seconds: float, *, traced: bool,
          smoke: bool = False) -> Run:
    """Tables, database and model for one run; no query runs yet."""
    from repro.core.database import IPDB
    from repro.relational.table import Table
    cfg, mix = plan["config"], plan["mix"]
    if not smoke:
        check_program_config(cfg, plan["family"])
    run = Run(plan)
    tab = mix["table"]
    seen: set = set()
    if mix["loop"] == "closed":
        rows = traffic.closed_loop_rows(mix, seed, mix["max_queries"], seen)
        per = mix["rows_per_query"]
        run.slices = [list(range(k * per, (k + 1) * per))
                      for k in range(mix["max_queries"])]
    else:
        run.schedule = traffic.open_loop_schedule(mix, seed, seconds, seen)
        rows = run.schedule["rows"]
    warm = traffic.warmup_rows(mix, seed, len(rows), seen)
    all_rows = rows + warm
    run.text_ids = {r[tab["text_column"]]: r[tab["key"]] for r in all_rows}
    run.warm_ids = [r[tab["key"]] for r in warm]
    db = IPDB(session_options=dict(mix.get("session_options", {})))
    db.register_table(tab["name"], Table.from_rows(all_rows,
                                                   traffic.schema_of(tab)))
    db.sql(f"CREATE LLM MODEL m PATH '{cfg['serve']['path']}' ON PROMPT "
           "OPTIONS "
           + _options_sql(model_options(cfg, mix, seed, smoke)))
    run.db = db
    run.tap = Tap(traced)
    run.tap.install()
    return run


def _sql(query: dict, ids: List[int], table: str) -> str:
    return string.Template(query["sql"]).substitute(
        table=table, lo=min(ids), hi=max(ids) + 1, id=ids[0])


def warm_up(run: Run) -> None:
    """Run every query kind of the mix over the warm-up rows, whose
    lengths span the mix's, so that every program the window uses is
    compiled (or loaded) before it opens."""
    mix = run.plan["mix"]
    tab = mix["table"]["name"]
    ids = run.warm_ids
    for q in mix["queries"]:
        if mix["loop"] == "closed":
            run.db.sql(_sql(q, ids, tab))
        else:
            for k in ids:
                _drain(run.db.stream(_sql(q, [k], tab), tenant="warm"))


def _drain(stream):
    parts = list(stream.chunks())
    table = parts[0] if parts else None
    for p in parts[1:]:
        table = table.concat(p)
    return table, stream.stats


def _stats_dict(st) -> dict:
    return {f.name: getattr(st, f.name) for f in dataclasses.fields(st)}


def closed_window(run: Run, seconds: float) -> dict:
    """Back-to-back rounds, each running every query kind of the mix once
    over a fresh slice; every round that starts before the window closes
    is counted and runs to its end, so each run counts whole rounds."""
    mix = run.plan["mix"]
    tab = mix["table"]["name"]
    out = []
    start = time.perf_counter()
    close = start + seconds
    k = 0
    while time.perf_counter() < close:
        for q in mix["queries"]:
            if k >= len(run.slices):
                raise RuntimeError(f"the mix holds {len(run.slices)} "
                                   f"queries; raise max_queries")
            t0 = time.perf_counter()
            rec = {"kind": q["kind"], "query": q, "ids": run.slices[k],
                   "due": t0, "start": t0}
            try:
                with run.tap.span("query"):
                    r = run.db.sql(_sql(q, run.slices[k], tab))
                rec.update(table=r.table, stats=_stats_dict(r.stats),
                           error=None)
            except Exception as e:                  # reported, not raised
                rec.update(table=None, stats=None, error=repr(e))
            rec["end"] = time.perf_counter()
            out.append(rec)
            k += 1
    end = max([r["end"] for r in out] + [close])
    return {"start": start, "close": close, "end": end, "queries": out,
            "late": []}


def open_window(run: Run, seconds: float) -> dict:
    """Queries sent at their due times from client threads, each through
    ``IPDB.stream``; latency runs from the due time to the last chunk."""
    mix = run.plan["mix"]
    tab = mix["table"]["name"]
    q = mix["queries"][0]
    sched = run.schedule
    recs = [{"kind": q["kind"], "query": q, "ids": [int(k)],
             "tenant": f"t{int(t)}", "table": None, "stats": None,
             "error": "not finished", "end": None}
            for k, t in zip(sched["ids"], sched["tenants"])]
    streams: Dict[int, object] = {}

    def client(i: int) -> None:
        rec = recs[i]
        rec["start"] = time.perf_counter()
        try:
            with run.tap.span("stream"):
                s = run.db.stream(_sql(q, rec["ids"], tab),
                                  tenant=rec["tenant"])
                streams[i] = s
                table, st = _drain(s)
            rec.update(table=table, stats=_stats_dict(st),
                       error="cancelled" if st.cancelled else None)
        except Exception as e:                      # reported, not raised
            rec["error"] = repr(e)
        rec["end"] = time.perf_counter()

    late = []
    pool = ThreadPoolExecutor(max_workers=int(mix["open"]["clients"]))
    futures = []
    start = time.perf_counter()
    try:
        for i, d in enumerate(sched["due"]):
            due = start + float(d)
            recs[i]["due"] = due
            wait = due - time.perf_counter()
            if wait > 0:
                with run.tap.span("await_arrival"):
                    time.sleep(wait)
            late.append(time.perf_counter() - due)
            futures.append(pool.submit(client, i))
        close = start + seconds
        deadline = close + OPEN_GRACE_S
        for f in futures:
            try:
                f.result(timeout=max(0.0, deadline - time.perf_counter()))
            except Exception:                       # timed out: cancelled
                pass
        for i, s in list(streams.items()):
            if recs[i]["end"] is None:
                s.cancel("window grace expired")
    finally:
        pool.shutdown(wait=True, cancel_futures=True)
    end = max([r["end"] for r in recs if r["end"] is not None] + [close])
    return {"start": start, "close": close, "end": end, "queries": recs,
            "late": late}


# -- checks ---------------------------------------------------------------------
def check_types(t) -> Optional[str]:
    """Every value of the result has its column's schema type."""
    for col, typ in t.schema.items():
        vals = t.cols[col]
        if typ == "BOOLEAN":
            ok = vals.dtype == np.bool_
        elif typ == "INTEGER":
            ok = np.issubdtype(vals.dtype, np.integer)
        elif typ == "VARCHAR":
            ok = all(isinstance(v, str) for v in vals)
        else:
            return f"unexpected column type {col} {typ}"
        if not ok:
            return f"column {col} is not all {typ}"
    return None


def answers_of(records: List[dict], text_ids: Dict[str, int],
               text_col: str) -> Dict[int, List[object]]:
    """Each row's served answers (output objects), from the dispatch tap."""
    out: Dict[int, List[object]] = {}
    for rec in records:
        for rows, text, nr in zip(rec["rows"], rec["texts"],
                                  rec["num_rows"]):
            try:
                objs = json.loads(text or "")
            except ValueError:
                objs = None
            if nr == 1:
                objs = [objs]
            for i, row in enumerate(rows or []):
                o = objs[i] if isinstance(objs, list) and i < len(objs) \
                    else None
                out.setdefault(text_ids.get(row.get(text_col), -1),
                               []).append(o)
    return out


def check_query(rec: dict, answers: Dict[int, List[object]]) -> Optional[str]:
    """The SQL result of one query against what the model served: each
    input row answered once, values typed, a projection returning every row
    with its answer, a select returning exactly the rows answered TRUE."""
    if rec["error"]:
        return rec["error"]
    st = rec["stats"]
    bad = {c: st[c] for c in FAILURE_COUNTERS if st[c]}
    if bad or st["cancelled"]:
        return f"failed calls {bad}"
    t = rec["table"]
    err = check_types(t)
    if err:
        return err
    name, typ = rec["query"]["output"]
    got = {int(i): v for i, v in zip(
        t.cols["id"], t.cols[name] if name in t.cols else [None] * len(t))}
    if len(got) != len(t):
        return "result repeats an id"
    want = {}
    for i in rec["ids"]:
        a = answers.get(i, [])
        if len(a) != 1 or not isinstance(a[0], dict) or name not in a[0]:
            return f"row {i} answered {len(a)} times: {a[:2]}"
        want[i] = a[0][name]
    if rec["query"]["kind"] == "select":
        expect = {i for i, v in want.items() if v is True}
        if set(got) != expect:
            return f"select returned {sorted(got)}, answered TRUE {sorted(expect)}"
        return None
    if set(got) != set(want):
        return f"returned ids {sorted(got)[:8]} of {sorted(want)[:8]}"
    wrong = [i for i in want if got[i] != want[i]]
    return f"rows {wrong[:8]} differ from their answers" if wrong else None


def requests_of(records: List[dict]) -> List[dict]:
    """One entry per served request: the token ids the engine read (BOS,
    then the prompt's bytes) and what it served."""
    out = []
    for rec in records:
        for p, text, nr, lg in zip(rec["prompts"], rec["texts"],
                                   rec["num_rows"], rec["logits"]):
            out.append({"prompt": [reference.BOS] + list(p.encode()),
                        "text": text or "", "num_rows": nr, "logits": lg,
                        "schema": rec["schema"], "max_str": rec["max_str"]})
    return out


def sample_requests(reqs: List[dict], seed: int) -> List[dict]:
    """The longest request, then requests drawn from the seed until some
    hundreds of served tokens, and enough tokens that had a choice, are in
    the sample."""
    if not reqs:
        return []
    rng = np.random.default_rng([seed, 4])
    size = [len(r["prompt"]) + len(r["text"]) for r in reqs]
    first = int(np.argmax(size))
    order = [first] + [int(i) for i in rng.permutation(len(reqs))
                       if i != first]
    pick, served, chosen = [], 0, 0
    for i in order:
        if (served >= SAMPLE_TOKENS and chosen >= SAMPLE_CHOICES) or \
                len(pick) >= SAMPLE_MAX_REQUESTS:
            break
        r = reqs[i]
        pick.append(r)
        served += len(r["text"]) + 1
        chosen += len(reference.choices(r["schema"], r["num_rows"],
                                        r["max_str"], r["text"]) or ())
    return pick


def compare_logits(sample: List[dict], cfg: dict, seed: int, family, *,
                   control: bool = False) -> dict:
    """The served requests against the family's float32 reference
    (``family.make_weights``, ``family.logits_at``), re-run over each
    prompt and its served tokens.  ``gap``: the widest amount by which a
    served token's reference logit lies below the reference's best allowed
    token.  ``err``: the largest difference between a logit the program
    sampled from and the reference's, over every served position and byte
    id.  With ``control``, also ``control_gap`` and ``control_err``: the
    same two numbers for the float8 reference put in the program's place,
    at the same positions of the same requests."""
    w = family.make_weights(cfg, model_seed(seed))
    out = {"gap": 0.0, "err": 0.0, "control_gap": 0.0, "control_err": 0.0,
           "positions": 0, "requests": len(sample), "off_grammar": 0,
           "unrecorded": 0}
    for r in sample:
        ch = reference.choices(r["schema"], r["num_rows"], r["max_str"],
                               r["text"])
        served = list(r["text"].encode()) + [reference.EOS]
        got = r["logits"]
        if ch is None:
            out["off_grammar"] += 1
            continue
        if got is None or len(got) != len(served):
            out["unrecorded"] += 1
            continue
        tokens = r["prompt"] + served[:-1]
        at = [len(r["prompt"]) - 1 + i for i in range(len(served))]
        idx = [i for i, _ in ch]
        allowed = [a for _, a in ch]
        picks = np.array([served[i] for i in idx])
        ref = family.logits_at(w, cfg, tokens, at)[:, :reference.BYTE_IDS]
        out["gap"] = max(out["gap"], reference.widest_gap(ref[idx], picks,
                                                          allowed))
        out["err"] = max(out["err"], float(np.abs(got - ref).max()))
        out["positions"] += len(idx)
        if control:
            low = family.logits_at(w, cfg, tokens, at, fp8=True)[
                :, :reference.BYTE_IDS]
            lp = np.array([a[int(np.argmax(row[list(a)]))]
                           for row, a in zip(low[idx], allowed)])
            out["control_gap"] = max(out["control_gap"], reference.widest_gap(
                ref[idx], lp, allowed))
            out["control_err"] = max(out["control_err"],
                                     float(np.abs(low - ref).max()))
    del w
    return out


def free_program(run: Run) -> None:
    """Drop every reference to the program's engines and device arrays."""
    run.tap.uninstall()
    run.db.close()
    run.db._jax_engines.clear()
    run.db = None
    gc.collect()


# -- the whole run --------------------------------------------------------------
def run_cell(plan: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, smoke: bool = False,
             after_window: Optional[Callable] = None,
             control: bool = False) -> dict:
    """Set up, warm up, measure, check; returns the result line's dict.
    ``t_start`` is when the process began its set-up.  ``after_window``
    (tests) sees the run between the window and the checks.  With
    ``control``, the float8 reference's logits are judged in the program's
    place, and ``out["control"]`` holds both sides' readings."""
    import jax
    mix, cfg, family = plan["mix"], plan["config"], plan["family"]
    run = build(plan, seed, seconds, traced=trace, smoke=smoke)
    clock = CompileCounter()
    warm_up(run)
    dev = jax.devices()[0]
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
    clock.names.clear()
    setup_s = time.perf_counter() - t_start
    run.tap.recording = True
    if trace:
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    with run.tap.span("window"), HostWatch() as host:
        window = (closed_window if mix["loop"] == "closed" else open_window)(
            run, seconds)
    if trace:
        jax.profiler.stop_trace()
    clock.active = False
    run.tap.recording = False
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    compiles = clock.count
    if compiles:
        log(f"compiled in the window: {clock.names}")
    if after_window is not None:
        after_window(run, window)

    records = [r for r in run.tap.records if r["t0"] >= window["start"]]
    text_col = mix["table"]["text_column"]
    answers = answers_of(records, run.text_ids, text_col)
    problems = {}
    for i, rec in enumerate(window["queries"]):
        err = check_query(rec, answers)
        rec["ok"] = err is None
        if err:
            problems[i] = err
    reqs = requests_of(records)
    free_program(run)

    ref_cfg = reference_config(cfg, smoke, family)
    gaps = compare_logits(sample_requests(reqs, seed), ref_cfg, seed, family,
                          control=control)
    limits = plan["limits"]
    # the control's logits are judged in the program's place
    src = "control_" if control else ""
    checks = {
        "queries_wrong": {"value": len(problems), "limit": 0},
        "answers_off_grammar": {"value": gaps["off_grammar"], "limit": 0},
        "answers_unrecorded": {"value": gaps["unrecorded"], "limit": 0},
        "logit_err": {"value": gaps[src + "err"],
                      "limit": limits["max_logit_err"]},
        "logit_gap": {"value": gaps[src + "gap"],
                      "limit": limits["max_logit_gap"]},
    }
    correct = (gaps["positions"] > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    for i, err in list(problems.items())[:5]:
        log(f"query {i} wrong: {err}")

    trace_red = None
    if trace:
        trace_red = trace_reduce.reduce(trace_reduce.read(
            trace_reduce.find_xplane(str(TRACE_DIR)),
            **TRACE_LINES[dev.platform]))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    dims = family.Dims.of(ref_cfg)
    ctx = {"setup_s": setup_s, "loop": mix["loop"], "window": window,
           "queries": window["queries"], "requests": reqs,
           "radix_hit_tokens": sum(r["radix_hit_tokens"] for r in records),
           "kv_layout": model_options(cfg, mix, seed, smoke).get(
               "kv_layout", "dense"),
           "compiles_in_window": compiles, "dims": dims,
           "peaks": None if smoke else peaks.peaks_for(dev.device_kind),
           "memory_peak_bytes": mem, "trace": trace_red}
    names = plan["per_layer"] if trace else plan["end_to_end"]
    metrics = {}
    for m in names:
        v = plan["readers"][m["name"]](ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    if trace_red:
        device.update(busy_s=trace_red["busy_s"],
                      window_s=trace_red["window_s"])
    failed = sum(1 for q in window["queries"] if not q["ok"])
    out = {"correct": bool(correct), "attempted": len(window["queries"]),
           "failed": failed, "metrics": metrics, "device": device}
    if trace_red:
        out["breakdown"] = trace_red["breakdown"]
    late = window["late"]
    if late:
        out["generator"] = {"late_p90_s": float(np.percentile(late, 90)),
                            "late_max_s": float(max(late))}
    out["host"] = host.summary()
    if control:
        out["control"] = gaps
    out["checks"] = checks
    return out
