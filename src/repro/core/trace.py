"""The program's own spans, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation`` named ``ipdb.<name>``: it
lands on the host thread's line of the same trace that holds the device's
operations, so a device gap can be read against what the host was doing.
The profiler session is the only switch: with no session open a span costs
well under a microsecond, so spans stay in the code unconditionally.  Spans
are opened per query stage, per dispatch, per decode tick and per slot fill,
never per row or per token.

Waits are spans whose name starts with ``await_``: they name an idle device
gap only where no work span is open.

Every span the program opens (the name after ``ipdb.``):

SQL front end, planner (``core/database.py``)
    ``sql.parse``        statement text to AST
    ``sql.bind``         AST to logical plan
    ``sql.optimize``     rewrites and ordering (a pilot dispatch nests here)
    ``await_plan_lock``  a stream waiting for the plan lock
service (``core/predict.py``, ``core/service.py``)
    ``predict.marshal``  cache probe and prompt building for one chunk
    ``predict.extract``  answers parsed into typed columns for one chunk
    ``service.dispatch`` one executor call (metadata: requests, session)
    ``await_result``     a thread blocked on a handle another thread runs
engine (``serving/engine.py``, ``serving/scheduler.py``)
    ``engine.run``       one batcher run or ``generate`` call
    ``engine.tick``      one decode-loop iteration, slot fills included
    ``engine.fill``      one slot fill (batcher)
    ``engine.radix_match``, ``engine.radix_insert``  radix tree lookups
    ``engine.prefill``   prefill launch through the host copy of its logits
    ``engine.splice``    the dense batcher's eager cache splice
    ``engine.sample``    grammar masks and token choice for the batch
    ``engine.mask``      the grammar masks alone
    ``engine.advance``   grammar advance and completion of the live rows
    ``engine.cow``       copy-on-write of shared pages (paged batcher)
    ``engine.step``      decode launch through the host copy of its logits
    ``engine.evict``     freeing pool pages held by the prefix caches
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "ipdb."

SPANS = (
    "sql.parse", "sql.bind", "sql.optimize", "await_plan_lock",
    "predict.marshal", "predict.extract", "service.dispatch", "await_result",
    "engine.run", "engine.tick", "engine.fill", "engine.radix_match",
    "engine.prefill", "engine.splice", "engine.radix_insert",
    "engine.sample", "engine.mask", "engine.advance", "engine.cow",
    "engine.step", "engine.evict",
)


def span(name: str, **meta) -> TraceAnnotation:
    """Context manager for one program span ``ipdb.<name>``; ``meta``
    becomes the span's metadata in the trace."""
    return TraceAnnotation(PREFIX + name, **meta)
