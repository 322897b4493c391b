"""Request-scoped correlation: one id threads a request through every
telemetry channel it touches.

The serving arc (serve.py spool, parallel/queue.py fleet leases) made
the telemetry pillars *per-host, per-run* — a client's request fans out
into span records, health digests, trace spans, failure-journal entries
and a ``done/`` response, possibly on different hosts, with nothing
tying them back to the request. This module is that tie: serve.py
installs the request id thread-locally around a request's videos
(:func:`use_request`), and every emitter that writes a per-video
artifact reads it back with :func:`current_request_id`:

  ==============================  =====================================
  ``_telemetry.jsonl`` span       ``request_id`` field (spans.py;
                                  ``video_span.schema.json``)
  ``_health.jsonl`` digest        ``request_id`` field (health.py;
                                  ``feature_health.schema.json``)
  ``_failures.jsonl`` record      ``request_id`` field (utils/faults.py,
                                  only when a request is in scope)
  ``_trace.json`` span            ``rid`` on EVERY complete event, the
                                  decode-ahead thread's included
                                  (telemetry/trace.py; the ``request``
                                  arg on ``video_attempt`` stays)
  fleet-queue lease               ``request_id`` stamp on the claim
                                  record (parallel/queue.py)
  ``done/{id}.json`` response     the id IS the filename (serve.py)
  ==============================  =====================================

so ``grep -r <request_id>`` over an output root (or
``vft-fleet --request <id>``) retrieves every artifact one request
produced on any host.

Outside serve mode nothing installs a request, :func:`current_request_id`
returns None, and the correlated fields serialize as null/absent —
batch-run artifacts are unchanged except for the one nullable field the
schemas declare. The read is a single thread-local ``getattr``, the same
cost class as :func:`~.spans.current_span`.

Propagation is thread-local on purpose: one request's videos run
sequentially on the serve worker thread that claimed it (serve.py
``_process``), and decode-ahead producer threads re-install the
consumer's span (``use_span``) and its request (``use_request``,
utils/io.py ``Prefetcher``).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Iterator, Optional

_tls = threading.local()


def current_request_id() -> Optional[str]:
    """The request id installed on THIS thread, if any (one getattr)."""
    return getattr(_tls, "request_id", None)


def tenant_of(request_id: Optional[str]) -> Optional[str]:
    """The tenant component of a gateway-minted request id.

    The gateway (gateway.py) mints ids as ``{tenant}-{rid}`` — tenant
    names are ``[a-z0-9_]+`` (dash-free, enforced at tenant-table load)
    and the random suffix is dash-free hex, so the first ``-`` splits
    unambiguously. Spool-direct clients use plain ``uuid4().hex`` ids
    with no dash: those (and None) return None — the single-implicit-
    tenant world keeps working untouched."""
    if not request_id:
        return None
    head, sep, rest = str(request_id).partition("-")
    return head if sep and head and rest else None


def current_tenant() -> Optional[str]:
    """Tenant of the request installed on THIS thread, if any — how the
    feature cache's ``cache_scope=tenant`` keys entries per tenant
    without any plumbing through the extractor stack."""
    return tenant_of(current_request_id())


@contextmanager
def use_request(request_id: Optional[str]) -> Iterator[None]:
    """Install ``request_id`` thread-locally for a block — serve.py
    wraps each claimed request's video loop in this, so every per-video
    emitter below it correlates without new plumbing through the
    extractor stack."""
    prev = getattr(_tls, "request_id", None)
    _tls.request_id = None if request_id is None else str(request_id)
    try:
        yield
    finally:
        _tls.request_id = prev
