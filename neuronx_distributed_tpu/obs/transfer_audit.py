"""Transfer audit: make the hot paths' no-sync invariant enforceable.

The async hot paths (``fit(prefetch=..., defer_metrics=...)`` and the
serving engine's pipelined decode) promise a transfer discipline: inside a
steady-state step, every host↔device crossing is *explicit* — batches enter
through :class:`~..data.prefetch.DevicePrefetcher`'s staged ``device_put``,
scalars leave through one packed :meth:`TransferAudit.fetch` — and nothing
crosses implicitly (a stray ``float(arr)`` / ``np.asarray(arr)`` /
``jit(numpy_arg)`` is a full device drain on a TPU).  This module turns that
promise from aspiration into a checked contract:

- :meth:`TransferAudit.section` wraps a hot region in ``jax.transfer_guard``
  — ``mode="forbid"`` makes any *implicit* transfer raise (tests run this
  way; production can too), while explicit ``device_put``/``device_get``
  stay allowed;
- :meth:`TransferAudit.fetch` / :meth:`TransferAudit.put` are the sanctioned
  explicit crossings: they count into the registry
  (``transfer/explicit_fetches_total`` / ``transfer/explicit_puts_total``)
  and time how long the host was blocked waiting on the device
  (``transfer/fetch_wait_ms`` plus a per-subsystem
  ``<label>/host_blocked_ms`` histogram) — so "one packed fetch per step"
  is assertable from metrics, and ``host_blocked_frac`` is derivable from
  artifacts alone.

Backend caveat (why ``forbid`` + counting, not counting alone): XLA's
transfer guard fires for host→device transfers on every backend, but
device→host reads of CPU-backed arrays are zero-copy and never trip it —
so on the CPU test mesh the d2h side of the invariant is enforced by
accounting (exactly N explicit fetches, none elsewhere) while h2d is
enforced by the real guard; on TPU ``forbid`` enforces both for real.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Optional

import jax

from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

MODES = ("off", "observe", "forbid")

# metric names (the obs.schemas.REGISTRY_METRICS contract)
FETCHES_TOTAL = "transfer/explicit_fetches_total"
PUTS_TOTAL = "transfer/explicit_puts_total"
FETCH_WAIT_MS = "transfer/fetch_wait_ms"
GUARDED_SECTIONS_TOTAL = "transfer/guarded_sections_total"


class TransferAudit:
    """Per-run transfer accountant + optional transfer-guard enforcer.

    ``registry`` (an ``obs.MetricRegistry``) receives the counters and
    host-blocked histograms; ``None`` keeps the audit free (time is still
    accumulated on :attr:`blocked_s` for callers that report a fraction
    directly).  ``mode``:

    - ``"off"``: :meth:`section` is a no-op (fetch/put still count);
    - ``"observe"``: sections are counted but transfers are not restricted;
    - ``"forbid"``: sections run under ``jax.transfer_guard("disallow")`` —
      an implicit transfer inside raises ``XlaRuntimeError`` naming the
      offending aval, explicit ``device_put``/``device_get`` pass.
    """

    def __init__(self, registry: Any = None, mode: str = "observe"):
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        self.registry = registry
        self.mode = mode
        self.blocked_s = 0.0   # cumulative host time spent inside fetch()
        self.fetches = 0
        self.puts = 0
        if registry is not None:
            from neuronx_distributed_tpu.obs import MS_BUCKETS

            self._ms_buckets = MS_BUCKETS
            registry.counter(FETCHES_TOTAL)
            registry.counter(PUTS_TOTAL)
            registry.counter(GUARDED_SECTIONS_TOTAL)
            registry.histogram(FETCH_WAIT_MS, MS_BUCKETS)

    @contextlib.contextmanager
    def section(self, name: str):
        """Enter a guarded hot section.  In ``forbid`` mode an implicit
        host↔device transfer inside raises; the section counter ticks in
        every mode but ``off`` so dashboards can see coverage."""
        if self.mode == "off":
            yield
            return
        if self.registry is not None:
            self.registry.counter(GUARDED_SECTIONS_TOTAL).inc()
        if self.mode == "forbid":
            with jax.transfer_guard("disallow"):
                yield
        else:
            yield

    def fetch(self, tree: Any, label: Optional[str] = None) -> Any:
        """THE sanctioned device→host read: one explicit ``jax.device_get``
        of (ideally packed) ``tree``.  Counts the fetch and observes the
        host-blocked wait into ``transfer/fetch_wait_ms`` and, when
        ``label`` is given, ``<label>/host_blocked_ms`` — one histogram per
        subsystem (``train``/``serving``) so overlap wins are graphable."""
        t0 = time.perf_counter()
        out = jax.device_get(tree)
        wait_s = time.perf_counter() - t0
        self.blocked_s += wait_s
        self.fetches += 1
        if self.registry is not None:
            self.registry.counter(FETCHES_TOTAL).inc()
            self.registry.histogram(
                FETCH_WAIT_MS, self._ms_buckets).observe(wait_s * 1e3)
            if label is not None:
                self.registry.histogram(
                    f"{label}/host_blocked_ms",
                    self._ms_buckets).observe(wait_s * 1e3)
        return out

    def put(self, tree: Any, shardings: Any = None) -> Any:
        """The sanctioned host→device write: explicit ``jax.device_put``
        (legal inside a ``forbid`` section, unlike handing numpy straight to
        a jitted call)."""
        out = (jax.device_put(tree) if shardings is None
               else jax.device_put(tree, shardings))
        self.puts += 1
        if self.registry is not None:
            self.registry.counter(PUTS_TOTAL).inc()
        return out
