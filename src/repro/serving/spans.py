"""Host spans of the serving path, on the profiler's clock.

Each phase of ``ContinuousBatchingScheduler.step()`` runs inside a
``jax.profiler.TraceAnnotation`` named in :data:`SPANS`, so a profiler
trace of a serving loop shows where the host's time went and which phase
each idle gap of the device falls under. The spans nest: ``serve.step``
holds the others, and no two of the others overlap.

Spans are always on. Without a profiler session a span costs about a
microsecond and its arguments are never formatted; with one, its
arguments (the ids of the requests a phase handles) land as stats of the
host event, so the spans of one request share its id.
"""
from __future__ import annotations

import jax

SPANS = (
    "serve.step",  # one step() call
    "serve.admit.plan",  # queue scan: context build, kv segments, prefix lookup, pages
    "serve.admit.pack",  # host arrays of one admission group, its padded width
    "serve.admit.dispatch",  # prefill / suffix prefill, admit-finish, slot write
    "serve.admit.sync",  # the group's first tokens copied to the host
    "serve.admit.commit",  # slot state, counters, prefix-cache insert
    "serve.draft",  # host drafting of speculative tokens (spec_k > 0)
    "serve.decode.upload",  # per-slot arguments of the decode call
    "serve.decode.dispatch",  # the decode (or verify) call
    "serve.decode.sync",  # its tokens copied to the host
    "serve.decode.commit",  # tokens appended, finished requests retired
)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (one of :data:`SPANS`), carrying
    ``args`` as its stats. ``set_metadata`` on the returned span adds
    arguments known only inside it."""
    return jax.profiler.TraceAnnotation(name, **args)
