"""The dense decoder family: pre-norm blocks of rotary attention with
grouped key/value heads and a SwiGLU MLP (OLMo, Yi).

A family is the part of the benchmark that knows a model's architecture.
The harness loads ``bench/families/<family>.py`` by the ``family`` key of a
configuration file; a configuration of a new architecture brings a module
beside this one, and nothing else of the harness changes.  The answer
grammar (``bench/reference.py``: ``choices``, ``widest_gap``, the byte
token ids), the traffic and the metric readers are shared by every family.
A family module exports:

``KEYS``
    The sizes the program's ``ModelConfig`` must match before a run (with
    the norm, rope and dtype keys every family is checked on), and those
    that a test's smoke variant takes over from the program.
``make_weights(cfg, seed)``
    The program's random-weight recipe, from the seed, in float32: every
    leaf the reference reads.
``logits_at(w, cfg, tokens, read_at, *, fp8=False)``
    The plain float32 forward pass over ``tokens`` under ``HIGHEST``
    precision, imports nothing of the program, returns the logits at the
    positions ``read_at`` as a (len(read_at), padded vocabulary) array.
    With ``fp8``, the control: every matmul's operands rounded to float8
    e4m3 with float32 accumulation.
``Dims``
    ``Dims.of(cfg)`` and, on it, ``request_flops(prompt, served)``, the
    (prefill, decode) operations of one request; ``linear_flops()``, the
    matmul operations of one token through every layer without the head;
    and ``decode_bytes(steps, contexts)``, the least bytes of that many
    decode steps over those keys.  These are least operations and least
    bytes, from the sizes alone.  A family whose least bytes depend on
    what the program did, such as the experts a step touched, gives the
    floor here and reads the sharper figure in a per-layer metric of its
    own, from the program's counters.

The dense family's code lives in ``bench/reference.py`` and
``bench/counts.py``; this module names it.
"""
from bench.counts import KEYS, Dims
from bench.reference import logits_at, make_weights

__all__ = ["KEYS", "Dims", "make_weights", "logits_at"]
