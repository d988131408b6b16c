"""Continuous-batching serving engine over the paged phase programs.

``ServingEngine.step()`` is the iteration-level scheduling loop (Orca,
OSDI '22): sweep cancellations/deadlines, admit queued requests into free
slots (block table + pages; the prompt's fresh pages are then computed by
the chunk loop, one chunk program a step), run ONE batched decode step with
per-slot cache offsets, sample each slot from its own request's rng stream
and sampler params, stream the tokens, and free the slots of finished
requests — so requests enter and leave the batch independently instead of
in lockstep, closing the utilization gap of the static ``generate`` batch
(slots no longer idle until the longest request finishes).

There is ONE path through the loop, the one every benchmark cell measures:

- **one KV representation** — a global page pool ``[NP, NKV, page, D]`` a
  layer plus per-slot block tables (``kvcache/``, ``serving/paged.py``).
  The pool's K/V rows have one writer, the block-table scatter inside the
  paged phase programs (scope ``kv_write`` in ``models/llama.py``), and
  ``copy_page`` for copy-on-write;
- **one prefill** — every fresh prompt rides the chunk loop
  (``prefill_chunk_pages``, at most ``prefill_chunk_tokens`` prompt tokens a
  step; one chunk of ``context_len`` when the caller names no size).  An
  exact repeated prompt skips compute: the prefix index hands back its
  pages and its prefill logits;
- **one decode loop, one step ahead** — with decode step N in flight,
  ``step()`` launches step N+1 BEFORE it reads N's tokens: each slot of N
  is fed N's sampled token where it lies on the device (a slot that began
  decoding since is fed the host's first token; one ``where`` over a
  ``[B]`` mask), its write offset and token index advanced by the row in
  flight, and only THEN is N fetched, its offsets committed, its stops
  detected and its slots released — so the host's whole turn a token
  (fetch, bookkeeping, staging, launch, stream callbacks, stats) runs
  under a queued program.  What the launch needs it has: a stop by LENGTH
  is a count the host holds, and the pages past an offset were reserved at
  admission.  A stop TOKEN or a non-finite row shows only in the fetch:
  such a request is found one step late and has one row in the step
  already queued — an *overrun*, whose token is never read and whose write
  lands in a decode page the slot still held (``_collect_decode``;
  ``serving/decode_overrun_rows_total``, beside
  ``serving/decode_runahead_total``: steps launched behind an unfetched
  one).  A speculative round keeps collect-then-dispatch: its offsets are
  the accepted counts, which only the fetch gives.  The whole per-step
  device→host traffic — sampled tokens and per-slot finite flags — is
  packed into ONE ``[2, B]`` array fetched with a single explicit
  ``device_get`` per step (counted by the
  :class:`~..obs.transfer_audit.TransferAudit`; host wait exported as
  ``serving/host_blocked_ms``).  The host→device direction is symmetric:
  the host's token feed and its mask, per-slot write offsets and token
  indices stage as one packed explicit ``device_put``, and the per-slot
  sampling state (keys / temperature / top-k / top-p) lives in device
  mirrors refreshed only when admission changes them.  A token's stream
  callback fires after the next step's dispatch, and the final token's
  callback sees its request already in a terminal state.

The reference the loop is held to is the solo ``generate`` of the same
weights: greedy outputs are token-identical to it (the per-row
mask/position machinery reproduces the scalar-offset math row by row, and
masked lanes contribute exactly zero probability), and sampled outputs
equal ``generate(..., rng=rng, request_ids=[rid])``.

The DRAFT model of speculative serving is the one user of the contiguous
phase functions (``prefill_one`` / ``insert_slot`` / ``decode_slots`` over
``empty_caches()``): its ``[B, T]`` row a slot makes rollback free.

Telemetry goes through the ``obs.MetricRegistry`` (queue-depth /
slot-occupancy gauges, TTFT and inter-token histograms, admission /
finish / cancel counters) and per-request ``serving_stats.jsonl`` records
validated by ``obs.schemas``.
"""

from __future__ import annotations

import json
import time
from collections import deque
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_tpu.obs import MS_BUCKETS, MetricRegistry, startup
from neuronx_distributed_tpu.obs.flight import FlightRecorder, StepAccount
from neuronx_distributed_tpu.obs.tracing import phase
from neuronx_distributed_tpu.obs.transfer_audit import TransferAudit
from neuronx_distributed_tpu.resilience.faults import fault_point, perturb
from neuronx_distributed_tpu.serving.driver import replay as driver_replay
from neuronx_distributed_tpu.serving.request import (
    PRIORITIES,
    PRIORITY_INTERACTIVE,
    Request,
    RequestOutput,
    RequestState,
)
from neuronx_distributed_tpu.kvcache.allocator import NULL_PAGE, PoolExhausted
from neuronx_distributed_tpu.kvcache.pool import GATHER_BYTES_TOTAL, cache_plan
from neuronx_distributed_tpu.kvcache.quant import QUANT_PAGES_TOTAL
from neuronx_distributed_tpu.kvcache.transfer import (
    ChainExport,
    TransferError,
    export_chain,
    import_chain,
)
from neuronx_distributed_tpu.models.hybrid import Launch, launch_counters
from neuronx_distributed_tpu.parallel.mesh import (
    TENSOR_AXIS,
    get_mesh,
    model_parallel_is_initialized,
)
from neuronx_distributed_tpu.parallel.moe import ExpertLoadBook
from neuronx_distributed_tpu.serving.paged import PagedKVManager
from neuronx_distributed_tpu.serving.scheduler import (
    DEFAULT_MAX_BATCH_WAIT_S,
    AdmissionError,
    BackpressureError,
    SLOInfeasible,
    SlotScheduler,
)
from neuronx_distributed_tpu.trace.engine import (
    SPEC_ACCEPT_SALT,
    SPEC_RESIDUAL_SALT,
    _filtered_logits,
    _sample_logits,
    _temperature_logits,
    request_rng,
)
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

SERVING_STATS_SCHEMA = "serving_stats/6"

FAIL_NON_FINITE = "non_finite_logits"

SHED_EXPIRED_BEFORE_PREFILL = "expired_before_prefill"


class _ChunkPrefill:
    """Per-slot progress of a paged chunked prefill: the admission-time
    prompt row and validity, the contiguous run of fresh ``(logical,
    physical)`` pages still to compute, and the FINAL chunk's logits (the
    prefill logits the first token samples from; no other chunk has any)."""

    __slots__ = ("req", "ids_row", "valid_row", "fresh", "next_i", "logits")

    def __init__(self, req, ids_row, valid_row, fresh):
        self.req = req
        self.ids_row = ids_row      # np [C] left-padded prompt ids
        self.valid_row = valid_row  # np [T] full-prompt key validity
        self.fresh = fresh          # [(lp, phys), ...] ascending, contiguous
        self.next_i = 0             # index into fresh of the next chunk page
        self.logits = None

    @property
    def pages_remaining(self) -> int:
        return len(self.fresh) - self.next_i


class _InFlight:
    """One decode program (or speculative round) launched and not yet
    fetched: its packed device payload, the rows it computed as ``(slot,
    request, occupancy generation)``, the sampled tokens where they lie on
    the device (what the step launched after it is fed; None for a
    speculative round, which keeps its last proposal instead), the weights
    version and ``moe_seq`` it was launched under, and its batch span —
    opened at the launch into an idle loop, or when the step before it is
    collected, so the spans tile as the device's work does."""

    __slots__ = ("packed", "active", "toks", "last_prop", "version",
                 "moe_seq", "step", "family", "span")

    def __init__(self, packed, active, version, moe_seq, step, family,
                 toks=None, last_prop=None):
        self.packed = packed
        self.active = active
        self.toks = toks
        self.last_prop = last_prop
        self.version = version
        self.moe_seq = moe_seq
        self.step = step
        self.family = family    # "decode_step" | "spec_round"
        self.span = None


#: the sampler's paths, in the order of ``_sample_rows``'s ``lax.switch``
SAMPLER_PATHS = ("greedy", "temperature", "filtered")
# the phases of a step, in the one place: each is a span ``nxd/serve/<name>``
# of the profiler's trace and a column of the step's record, both through
# ``ServingEngine._phase``.  ``step`` is the whole step (in the record: what
# no other phase owns); ``fetch`` is the blocking device->host read, inside
# ``collect`` or ``first_token``; ``first_token`` is a prompt's first-token
# tail (sample, blocking read, hand-over to decode) after its last chunk or
# inside ``admit`` on an exact prefix hit; ``tail`` is what follows
# ``finish`` (gauges, watchdog, health, ledger poll) and launches nothing
SERVE_PHASES = ("step", "admit", "prefill_chunk", "first_token", "dispatch",
                "collect", "fetch", "finish", "tail")


def _sampler_path(temperature, top_k, top_p):
    """Index into :data:`SAMPLER_PATHS` of the least work that serves these
    rows: 0 when no row samples, 1 when some do and none of those filters,
    2 otherwise.  ONE formula for the device's choice (traced, inside
    :func:`_sample_rows`) and the host's count of it (the engine's numpy
    mirrors): array methods only, so numpy stays on the host."""
    samples = temperature > 0.0
    filters = samples & ((top_k > 0) | (top_p < 1.0))
    return samples.any().astype(np.int32) + filters.any().astype(np.int32)


@jax.jit
def _sample_rows(logits, base_keys, tok_idx, temperature, top_k, top_p):
    """Row-wise sampler: every slot draws token ``tok_idx[b]`` from its own
    request stream (``fold_in(base_keys[b], tok_idx[b])`` — the
    per-token fold_in happens INSIDE the jit, so the hot decode loop pays
    zero per-slot host dispatches) with its own sampler params.  One
    compiled program serves any mix of greedy/sampled slots, and does only
    the work the BATCH asks for: the parameter vectors pick one branch of a
    ``lax.switch`` on the device (:func:`_sampler_path`; no host read) —
    argmax alone when no row samples, a categorical draw on the
    temperature-scaled logits when no sampling row filters, and otherwise
    the full ``_sample_logits`` (two sorts and a gather over the
    vocabulary) for every row.  The choice sits outside the ``vmap`` — a
    ``cond`` under ``vmap`` is a ``select`` that runs both sides — and all
    three branches return the tokens the full one would, bit for bit.  The
    rows of slots that are not decoding must carry temperature 0 (the
    engine resets a slot's row when it parks it), or one finished sampled
    request holds the batch on the full branch.  Module-level jit so every
    engine over the same shapes shares one compile.

    Returns ``(tokens [B], finite [B])``: ``finite[b]`` is False when row
    ``b``'s logits contain NaN/Inf — computed inside the jit (a cheap
    reduction riding the same dispatch; the full ``[B, V]`` logits never
    cross to the host) so the engine can quarantine a numerically blown-up
    slot without poisoning its co-batch."""
    def greedy(lg, keys, idx, t, k, p):
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)

    def temperature_only(lg, keys, idx, t, k, p):
        def row(lg, key, idx, t):
            sampled = jax.random.categorical(
                jax.random.fold_in(key, idx), _temperature_logits(lg, t),
                axis=-1).astype(jnp.int32)
            return jnp.where(t > 0.0, sampled,
                             jnp.argmax(lg, axis=-1).astype(jnp.int32))
        return jax.vmap(row)(lg, keys, idx, t)

    def filtered(lg, keys, idx, t, k, p):
        return jax.vmap(lambda lg, key, idx, t, k, p: _sample_logits(
            lg, jax.random.fold_in(key, idx), t, k, p))(lg, keys, idx, t, k, p)

    with jax.named_scope("sample"):
        toks = jax.lax.switch(
            _sampler_path(temperature, top_k, top_p),
            (greedy, temperature_only, filtered),
            logits, base_keys, tok_idx, temperature, top_k, top_p)
        finite = jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)
        return toks, finite


@jax.jit
def _propose_rows(logits, base_keys, tok_idx, temperature, top_k, top_p):
    """Row-wise draft proposal: exactly :func:`_sample_rows`'s draw (same
    per-request ``fold_in(base_keys[b], tok_idx[b])`` stream, so with
    ``draft == target`` the proposals ARE the plain-sampling tokens), but
    additionally returns the per-row FILTERED draft logits — the q
    distribution the proposal was drawn from, which the speculative accept
    test needs verbatim."""
    def row(lg, key, idx, t, k, p):
        qf = _filtered_logits(lg, t, k, p)
        tok = _sample_logits(lg, jax.random.fold_in(key, idx), t, k, p)
        return tok, qf, jnp.all(jnp.isfinite(lg.astype(jnp.float32)))

    with jax.named_scope("sample"):
        return jax.vmap(row)(logits, base_keys, tok_idx, temperature, top_k,
                             top_p)


@jax.jit
def _spec_accept(vlogits, q_filt, props, base_keys, tok_idx, temperature,
                 top_k, top_p, draft_finite):
    """Per-slot draft-k-verify accept/commit for one speculative round, all
    on device — the batched (per-slot, no lockstep) twin of the solo
    ``speculative_generate`` round.

    ``vlogits [B, S=k+1, V]`` are the target's raw verification logits
    (position ``i`` judges proposal ``i+1`` — the shifted-logits trick);
    ``q_filt [B, k, V]`` the filtered draft distributions; ``props [B, k]``
    the proposals; ``tok_idx [B]`` the generated-token index of each slot's
    first proposal.  Greedy rows accept while the target argmax agrees and
    take the target's token at the first disagreement (or the bonus
    position); sampled rows run the standard Leviathan et al. accept/reject
    — accept with prob ``min(1, p/q)`` on per-token salted coins, resample
    the first rejection from the residual ``norm(max(p - q, 0))`` — so
    ``draft == target`` accepts everything and reproduces plain sampling
    bit-for-bit.

    Returns the round's ENTIRE device→host payload packed as one
    ``[k+3, B]`` int32 array: rows ``0..k`` the candidate commit tokens
    (proposals 0..a-1 then the corrective/bonus token at row ``a``; rows
    past ``a`` are garbage the host ignores), row ``k+1`` the accept count
    ``a``, row ``k+2`` the per-slot finite flag (target AND draft)."""
    K = props.shape[1]

    def row(pl, qf, pr, key, idx, t, tk, tp):
        finite = jnp.all(jnp.isfinite(pl.astype(jnp.float32)))
        greedy = jnp.argmax(pl, axis=-1).astype(jnp.int32)  # [K+1]
        pf = _filtered_logits(pl, t, tk, tp)                # [K+1, V]
        p_probs = jax.nn.softmax(pf[:K], axis=-1)           # [K, V]
        q_probs = jax.nn.softmax(qf, axis=-1)               # [K, V]
        px = jnp.take_along_axis(p_probs, pr[:, None], axis=-1)[:, 0]
        qx = jnp.take_along_axis(q_probs, pr[:, None], axis=-1)[:, 0]
        coin_keys = jax.vmap(lambda j: jax.random.fold_in(
            jax.random.fold_in(key, SPEC_ACCEPT_SALT), idx + j)
        )(jnp.arange(K, dtype=jnp.int32))
        u = jax.vmap(jax.random.uniform)(coin_keys)         # [K]
        acc_sampled = u < jnp.minimum(1.0, px / jnp.maximum(qx, 1e-20))
        acc_greedy = greedy[:K] == pr
        accept = jnp.where(t > 0.0, acc_sampled, acc_greedy)
        lead = jnp.cumprod(accept.astype(jnp.int32))
        a = jnp.sum(lead).astype(jnp.int32)  # leading accepts, 0..K
        # position a's extra token: residual resample on a rejection,
        # one fresh target draw on a full accept (a == K)
        p_a = jnp.take(p_probs, jnp.minimum(a, K - 1), axis=0)
        q_a = jnp.take(q_probs, jnp.minimum(a, K - 1), axis=0)
        res = jnp.maximum(p_a - q_a, 0.0)
        res_sum = jnp.sum(res)
        # degenerate all-zero residual (p <= q everywhere off the sample)
        # falls back to p itself — both are exact draws from p
        dist = jnp.where(res_sum > 0, res / jnp.maximum(res_sum, 1e-20), p_a)
        corr_sampled = jax.random.categorical(
            jax.random.fold_in(
                jax.random.fold_in(key, SPEC_RESIDUAL_SALT), idx + a),
            jnp.log(jnp.maximum(dist, 1e-20))).astype(jnp.int32)
        # full-accept bonus: straight from p_K with the plain-sampling
        # token-index key — bit-identical to the non-speculative draw
        bonus_sampled = jax.random.categorical(
            jax.random.fold_in(key, idx + K), pf[K]).astype(jnp.int32)
        sampled_extra = jnp.where(a == K, bonus_sampled, corr_sampled)
        extra = jnp.where(t > 0.0, sampled_extra, jnp.take(greedy, a))
        commit = jnp.concatenate(
            [pr, jnp.zeros((1,), jnp.int32)]).at[a].set(extra)
        return commit, a, finite

    commit, acc, finite = jax.vmap(row)(
        vlogits, q_filt, props, base_keys, tok_idx, temperature, top_k, top_p)
    finite = jnp.logical_and(finite, draft_finite)
    return jnp.concatenate(
        [commit.T.astype(jnp.int32), acc[None, :].astype(jnp.int32),
         finite[None, :].astype(jnp.int32)], axis=0)


@jax.jit
def _pack_tokens(toks, finite):
    """Pack the decode step's whole device→host payload into one ``[2, B]``
    int32 array so the engine pays exactly ONE host fetch per step.  A
    separate tiny jit (not fused into :func:`_sample_rows`) so the sampler
    program is the same one the prefill's first token runs."""
    with jax.named_scope("pack_tokens"):
        return jnp.stack([toks.astype(jnp.int32), finite.astype(jnp.int32)])


@jax.jit
def _feed_tokens(prev_toks, host_tok, from_host):
    """The token feed of a decode step launched while the step before it is
    still in flight: per slot, that step's sampled token where it lies on
    the device (``prev_toks [B]``, never read by the host first), or the
    host's token (``host_tok [B, 1]``) for a slot that began decoding since
    — its first token came from the prefill's logits.  ``from_host [B]``
    rides the step's one packed put."""
    with jax.named_scope("feed_tokens"):
        return jnp.where(from_host, host_tok[:, 0],
                         prev_toks.astype(jnp.int32))[:, None]


#: module-level jits shared by every engine in the process: their compiles
#: are invisible to the per-model _CompiledLRU accounting, so the ledger-on
#: engine polls their jit cache sizes per step instead (growth after
#: warmup = a silent mid-serve recompile, the PR-9 ``_sample_rows``
#: pathology)
_MODULE_JITS = (("sample_rows", _sample_rows),
                ("propose_rows", _propose_rows),
                ("spec_accept", _spec_accept),
                ("pack_tokens", _pack_tokens),
                ("feed_tokens", _feed_tokens))


def _module_jit_sizes() -> dict:
    """{name: jit cache size} for the shared sampler jits (absent when the
    jax version exposes no ``_cache_size``)."""
    from neuronx_distributed_tpu.obs.compile_ledger import jit_cache_size

    out = {}
    for name, fn in _MODULE_JITS:
        n = jit_cache_size(fn)
        if n is not None:
            out[name] = n
    return out


def replay_trace(engine: "ServingEngine", arrivals, requests,
                 on_output=None, clock=time.monotonic, sleep=time.sleep):
    """Replay an arrival trace through a live engine — the historical name
    for :func:`~.driver.replay`, which since the fleet PR drives a
    :class:`~.fleet.FleetRouter` through the same loop.  Kept as the
    engine-flavored alias; see ``serving/driver.py`` for the contract
    (including the crash flight dump on an unhandled exception)."""
    return driver_replay(engine, arrivals, requests, on_output=on_output,
                         clock=clock, sleep=sleep)


class ServingEngine:
    """Continuous-batching engine over a :class:`~..trace.ParallelInferenceModel`.

    ``model`` must expose the paged serving surface (``make_page_pool`` /
    ``prefill_chunk_pages`` / ``decode_pages`` / ``insert_valid``) —
    ``ParallelInferenceModel`` does; exported ``LoadedInferenceModel``
    artifacts carry only the scalar-offset context/decode pair and are
    rejected up front.

    ``rng`` seeds the per-request sampling streams
    (``fold_in(fold_in(rng, request_id), token_index)`` — the same streams
    ``generate(request_ids=...)`` draws from, so a sampled request's tokens
    are independent of its co-batch).  Greedy requests need no rng.

    ``stats_path`` appends one schema-checked ``serving_stats`` JSONL record
    per terminal request.  ``registry`` (an ``obs.MetricRegistry``) receives
    the serving gauges/histograms/counters; one is created when omitted so
    metrics are always available via :attr:`registry`.

    Hardening knobs (resilience PR):

    - ``max_queue`` bounds the admission queue — a full queue makes
      ``submit`` raise ``BackpressureError`` (transient, retryable; counted
      in ``serving/rejected_total``) so overload is rejected at the edge;
    - non-finite logits in a slot fail THAT request only (terminal state
      ``failed``, finish reason ``non_finite_logits``; the slot is freed and
      reusable, co-batched requests never see the poison) — counted in
      ``serving/failed_total``;
    - ``step_timeout_s`` arms the engine step watchdog: a ``step()`` call
      slower than the threshold logs a warning and counts into
      ``serving/slow_steps_total`` (every step's duration exports as the
      ``serving/step_ms`` histogram and ``serving/last_step_ms`` gauge);
    - every step keeps its own account (``obs.flight.StepAccount``: host
      time by phase, CPU time, time blocked in a fetch, time between steps;
      the stall rule; ``SERVE_PHASES`` names the phases) and leaves one flat
      record in a flight ring, hub or no hub; with ``obs`` (an
      ``obs.Observability`` hub) that ring is the hub's, ``replay_trace``
      dumps it on an unhandled exception, and the engine's metrics then
      ride the hub's registry unless one was passed explicitly.

    The decode loop runs one step ahead (see the module docstring): step
    N+1 is dispatched, from step N's tokens on the device, before step N is
    fetched, and all per-step host↔device traffic packs into one explicit
    fetch + one explicit put.  ``transfer_guard="forbid"`` wraps the steady
    decode section in ``jax.transfer_guard("disallow")``: an implicit
    transfer in the hot path raises instead of silently draining the
    device.  Fetch/put counts and ``serving/host_blocked_ms`` export in
    every mode.

    The KV cache is a global page pool plus per-slot block tables:
    ``page_size`` (required; it must divide ``context_len`` and
    ``max_total_len``) is the tokens a page holds and ``num_pages`` the
    pool's size — HBM is sized by ``num_pages``, not ``B * T``.  Left
    unset, ``num_pages`` is the pool in which every slot can hold
    ``max_total_len`` (``B * T / page_size`` pages and the NULL page).
    Admission gates on *pages free*, every terminal state reclaims its
    pages, and ``prefix_cache`` (on unless pages come back, below) shares
    page-aligned prompt
    prefixes across requests (an exact repeated prompt skips prefill
    compute entirely).  ``kvcache/*`` metrics (pool occupancy, prefix
    hit/miss, evictions) export through the registry.  Pages come in KINDS,
    one a causal window of the model's layers (``kvcache.pool.page_kinds``;
    most models have one): each kind has its allocator, its block table a
    slot and its page count — ``num_pages`` may name one a kind — and a kind
    whose window a row can outgrow takes pages as the writes reach them and
    gives them back, from the step's ``tail``, once the oldest row that can
    still be queried has moved past them — unless ``prefix_cache=True``,
    ``spec_k``, ``kv_quant`` or ``adapter_store`` asked for whole chains.
    What a model's layers keep, what that may be combined with and what is
    refused is ``kvcache.pool.cache_plan``'s to say.

    Speculative decoding (spec PR): ``draft=`` (a second
    ``ParallelInferenceModel`` sharing the target's tokenizer and serving
    shapes) + ``spec_k=`` turn every decode step into a batched per-slot
    draft-k-verify round — the serving generalization of the solo
    ``trace.speculative_generate``.  Accepted tokens
    scatter into block-table pages through the verify step itself, rejected
    tails roll back by host-side offset rewind against the worst-case
    ``spec_k``-token page reservation made at admission (no device copy),
    and stop tokens are detected inside an accepted run.  Greedy output is
    token-identical to the non-speculative engine; sampled acceptance uses
    the standard residual-distribution correction, so ``draft == target``
    reproduces plain sampling bit-for-bit.  Per-request acceptance rates
    land in ``serving_stats.jsonl`` and the ``serving/spec_*_total``
    counters (committed/rounds is the tokens-per-step headline).

    Multi-tenant serving (tenancy PR):

    - ``adapter_store=`` (a :class:`~..tenancy.AdapterStore`) serves many
      LoRA adapters from ONE compiled envelope: ``Request.adapter_id``
      names the adapter, admission pins it resident (paging its weight
      blocks through the store's refcounted allocator, LRU-evicting cold
      adapters), every decode step applies the per-slot deltas as one
      gathered low-rank einsum pair (S-LoRA-style), and every terminal
      state releases the pin.  Adapter 0 is the base model — an engine
      whose batch holds only adapter-0 requests is token-identical to the
      storeless engine.  Prefix-cache keys are salted per adapter, so
      prompt-page sharing stays exact within an adapter and never crosses
      adapters;
    - ``kv_quant="int8"`` stores KV pages int8 with per-page scale/zero
      (quantize-on-write, dequantize-in-the-gather; see
      ``kvcache.quant``), roughly doubling ``pages_for_budget`` at a
      bounded, parity-tested logit drift.  ``kvcache/quant_pages_total``
      counts quantized page writes.

    Stall-free SLO serving:

    - ``prefill_chunk_tokens=N`` (a multiple of ``page_size``; unset, one
      chunk of ``context_len``) is the width of the ONE chunk program and
      the prefill budget of a step: every fresh prompt is a
      Sarathi-style chunked prefill, at most ``N`` prompt tokens per
      engine step (page-aligned ``prefill_chunk_pages`` scatters at the
      slot's offset; a shorter span is right-padded), a PREFILLING slot
      co-exists with decoding slots inside one ``step()``, and the outputs
      are token-identical whatever the width (prefix-cache hits still
      skip resident chunks).  Co-batched decodes tick every step, so a
      smaller ``N`` keeps inter-token latency from spiking with a
      neighbor's prompt length.  Composes with ``spec_k`` (the draft row
      prefills whole at admission), ``kv_quant`` (chunk writes
      quantize-on-scatter) and
      ``adapter_store`` (chunks prefill under the request's adapter) —
      every pair is one parameterization of the same paged phase-fn
      family;
    - ``Request.priority`` ("interactive" | "batch") + EDF replace FCFS:
      interactive requests are granted first and may PREEMPT a decoding
      batch-tier victim when blocked on slots/pages (victim pages released
      transactionally, request requeued and re-prefilled later,
      token-identical); ``max_batch_wait_s`` bounds batch-tier wait — an
      over-bound head is promoted and becomes preemption-immune, so the
      batch tier always drains;
    - ``shed_infeasible=True`` sheds a request whose deadline the EWMA
      queue-wait + TTFT estimate already exceeds with the distinct
      ``SLOInfeasible`` signal at submit (counted in
      ``serving/shed_total``), and every prefill/chunk dispatch re-checks
      the deadline first (``serving/expired_before_prefill_total``) so a
      dead queue head never burns prefill compute.  Per-class TTFT and
      inter-token histograms (``serving/{ttft,intertoken}_ms_<class>``)
      carry the per-tier SLO story.

    Request-lifecycle tracing (tracing PR): ``tracer=`` (an
    ``obs.tracing.Tracer``, or a per-replica ``tracer.scoped(rid)`` in a
    fleet) records one span tree per request — root span submit→terminal,
    wait phases (queue, preempted park) from the scheduler, compute phases
    (prefill with per-chunk children and prefix-hit attrs, decode) from
    the engine, plus batch-level ``decode_step``/``spec_round`` spans with
    per-slot children.  Phase boundaries share single timestamps, so a
    request's phases tile its lifetime exactly (the ``obs_report --trace``
    waterfall sums to its ``serving_stats`` latency).  ``tracer=None``
    (default) is ZERO overhead: every call site is guarded, no span is
    ever allocated.  Terminal ``serving_stats`` records carry ``trace_id``
    linking them into ``trace_events.jsonl``.

    Fleet health monitor (this PR): ``health=`` (an
    ``obs.health.HealthMonitor``; defaults to ``obs.health_monitor`` when
    an ``Observability(health=...)`` hub is attached) evaluates its rule
    pack over this engine's registry on the step cadence — threshold /
    EWMA-trend / SLO burn-rate rules firing schema-checked ``alerts.jsonl``
    edges — and every terminal request feeds its per-class deadline
    attainment into the burn-rate windows.  ``health=None`` (default) is
    allocation-free: every call site is guarded, proven by the
    ``obs.health.ALERTS_EVALUATED`` counter.
    """

    @startup.phased("engine")
    def __init__(
        self,
        model: Any,
        *,
        rng: Optional[jax.Array] = None,
        registry: Optional[MetricRegistry] = None,
        stats_path: Optional[str] = None,
        eos_token_id: Optional[int] = None,
        clock: Callable[[], float] = time.monotonic,
        max_queue: Optional[int] = None,
        step_timeout_s: Optional[float] = None,
        obs: Any = None,
        transfer_guard: str = "off",
        page_size: int,
        num_pages: Optional[int] = None,
        prefix_cache: Optional[bool] = None,
        draft: Any = None,
        spec_k: int = 0,
        adapter_store: Any = None,
        kv_quant: Optional[str] = None,
        prefill_chunk_tokens: Optional[int] = None,
        max_batch_wait_s: Optional[float] = DEFAULT_MAX_BATCH_WAIT_S,
        shed_infeasible: bool = False,
        paged_kernel: Any = "auto",
        tracer: Any = None,
        compile_ledger: Any = None,
        memory_ledger: Any = None,
        health: Any = None,
    ):
        attrs = ("make_page_pool", "prefill_chunk_pages", "decode_pages",
                 "insert_valid")
        if spec_k:
            attrs += ("verify_pages",)
        if adapter_store is not None:
            attrs += ("decode_pages_lora", "make_adapter_pool",
                      "write_adapter_page")
        for attr in attrs:
            if not hasattr(model, attr):
                raise TypeError(
                    f"model {type(model).__name__} has no {attr!r}: the "
                    "continuous-batching engine needs the paged serving "
                    "surface of ParallelInferenceModel (exported artifacts "
                    "carry only the scalar-offset context/decode pair)")
        self.model = model
        cfg = model.config
        self.B = cfg.batch_size
        self.C = cfg.context_len
        self.T = cfg.max_total_len
        # speculative decoding (draft-k-verify): a co-batched draft model
        # proposes spec_k tokens per slot per round, one batched target
        # verification scores them all, accepted runs commit multi-token
        if (draft is None) != (spec_k == 0):
            raise ValueError(
                "speculative decoding needs BOTH draft= and spec_k= (got "
                f"draft={'set' if draft is not None else 'None'}, "
                f"spec_k={spec_k})")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self._spec_k = int(spec_k)
        self._draft_model = draft
        # multi-tenant serving (tenancy/): per-request LoRA adapters paged
        # through the adapter store; int8 KV pages double the pool at a
        # measured, bounded logit drift.  Both compose with speculative
        # decoding — the verify chunk is the same parameterized phase fn,
        # adapter-aware and requantizing.
        if kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant must be 'int8' or None, got {kv_quant!r}")
        self._adapters = adapter_store
        self._kv_quant = kv_quant
        # what the model's layers keep, and what that may be combined with
        # (kvcache.pool.cache_plan: it raises what is not carried through)
        mcfg = getattr(getattr(model, "module", None), "config", None)
        self._cache_plan = plan = cache_plan(
            mcfg, spec_k=spec_k, kv_quant=kv_quant,
            adapters=adapter_store is not None, prefix_cache=prefix_cache,
            tp=(get_mesh().shape[TENSOR_AXIS]
                if model_parallel_is_initialized() else 1),
            max_total_len=self.T)
        self._recurrent = plan.recurrent
        self._pageless = plan.pageless
        if spec_k:
            # the draft keeps a contiguous [B, T] row a slot (see
            # _prefill_draft_row): the one user of these phase functions
            for attr in ("prefill_one", "insert_slot", "decode_slots",
                         "empty_caches"):
                if not hasattr(draft, attr):
                    raise TypeError(
                        f"draft {type(draft).__name__} has no {attr!r}: the "
                        "draft needs the contiguous per-slot serving "
                        "surface of ParallelInferenceModel")
            dcfg = draft.config
            for f in ("batch_size", "context_len", "max_total_len"):
                if getattr(dcfg, f) != getattr(cfg, f):
                    raise ValueError(
                        f"target/draft serving shapes differ on {f}: "
                        f"{getattr(cfg, f)} vs {getattr(dcfg, f)}")
            dv = getattr(getattr(draft, "module", None), "config", None)
            if (mcfg is not None and dv is not None
                    and getattr(mcfg, "vocab_size", None)
                    != getattr(dv, "vocab_size", None)):
                raise ValueError(
                    f"target/draft vocab_size differ ({mcfg.vocab_size} vs "
                    f"{dv.vocab_size}): speculative decoding needs one "
                    "shared tokenizer")
        self.obs = obs
        if registry is None and obs is not None:
            registry = obs.registry
        self.registry = registry if registry is not None else MetricRegistry()
        # a routed model's expert loads, booked as they ride the step's
        # fetch (parallel.moe.ExpertLoadBook); None: the model is dense
        self._moe_book = (
            ExpertLoadBook(model, self.registry)
            if getattr(mcfg, "num_experts", 1) > 1
            and hasattr(model, "take_moe_stats") else None)
        # resource ledgers (obs.compile_ledger / obs.memory_ledger).  An
        # explicit compile ledger is attached to the MODEL (and the draft)
        # so the AOT phase-fn wrappers and every _CompiledLRU family report
        # to it — explicit wins over whatever a previous engine left there
        # (benches build several engines over one model sequentially), and
        # the attachment PERSISTS: a later ledger-less engine over the same
        # model keeps reporting to it, so when reusing a model across
        # independent measurement rungs, give EACH rung's engines (warm
        # passes included) that rung's ledger or a warm-declared previous
        # ledger would book the new rung's compiles as storms.
        # Ledgers-off (the default) stays allocation-free: every call site
        # below guards on `is not None`.
        self.compile_ledger = compile_ledger
        self.memory_ledger = memory_ledger
        # the step's own account (obs.flight): ONE ring of flat step
        # records, the hub's where a hub is given
        self._flight = (obs.flight if obs is not None
                        else FlightRecorder(registry=self.registry))
        self._account = StepAccount(SERVE_PHASES, self._flight,
                                    self.registry, "serving")
        if compile_ledger is not None:
            compile_ledger.attach(registry=self.registry, tracer=tracer,
                                  flight=self._flight,
                                  memory_ledger=memory_ledger)
            model.compile_ledger = compile_ledger
            if draft is not None:
                draft.compile_ledger = compile_ledger
        if memory_ledger is not None and memory_ledger.registry is None:
            memory_ledger.registry = self.registry
        # module-level sampler jits (_sample_rows & co) recompile only when
        # an argument's shape/dtype/placement changes — exactly the
        # mid-serve recompile the PR-9 perf fix chased.  With the ledger
        # on, step() polls their jit cache sizes (a few C++ attribute
        # reads) and books any growth as a compile event.
        self._jit_sizes = (_module_jit_sizes()
                          if compile_ledger is not None else None)
        # chunked prefill (Sarathi-style stall-free batching): a prompt's
        # fresh pages trickle into the pool a chunk a step — a PREFILLING
        # slot co-exists with decoding slots, and the chunk width bounds how
        # much prefill work any one step may do.  Left unset, one chunk
        # spans the context: the width a whole-prompt program compiles at
        self._chunk_tokens = (self.C if prefill_chunk_tokens is None
                              else prefill_chunk_tokens)
        if self._chunk_tokens < page_size \
                or self._chunk_tokens % page_size != 0:
            raise ValueError(
                f"prefill_chunk_tokens ({self._chunk_tokens}) must be a "
                f"positive multiple of page_size ({page_size}) — chunks "
                "are page-aligned so cached prefix pages can be skipped "
                "whole")
        # the KV cache (kvcache/ subsystem): a global page pool sized by
        # `num_pages` — left unset, the pool in which every slot can hold
        # max_total_len, and the NULL page — slots carry int32 block tables,
        # admission gates on pages free, repeated prompts share prefix pages
        # ... of each kind: a kind that gives pages back holds at most its
        # window, a chunk and a page of misalignment a slot
        # (``PagedKVManager.window_pages``)
        self._kv = PagedKVManager(
            num_slots=self.B, context_len=self.C, max_total_len=self.T,
            page_size=page_size, num_pages=num_pages,
            registry=self.registry, prefix_cache=plan.prefix_cache,
            spec_overshoot=self._spec_k, state_rows=self._recurrent,
            kinds=plan.page_kinds, chunk_tokens=self._chunk_tokens,
            free_behind=plan.free_behind, pageless=self._pageless)
        num_pages = self._kv.num_pages
        self._pages_freed = self._kv.frees
        self._chunking: dict = {}   # slot -> _ChunkPrefill in progress
        self._chunk_rr = 0          # budget-rotation cursor (fairness)
        # block-table-native paged decode (ops.paged_attention): "auto"
        # follows the model wrapper's resolved default (kernel when its
        # programs run on a TPU, gather elsewhere); explicit True/False
        # overrides per engine.  Gather-path steps account their [B, T]
        # K/V rematerialization into kvcache/gather_bytes_total — the
        # counter the kernel path keeps at ZERO (the int8 acceptance gate).
        if paged_kernel in ("auto", None):
            self._paged_kernel = bool(getattr(model, "paged_kernel", False))
        else:
            from neuronx_distributed_tpu.ops.paged_attention import (
                resolve_paged_kernel,
            )

            self._paged_kernel = resolve_paged_kernel(paged_kernel)
        # the model's sliding window, for the count of pages a decode walks
        # (of a model of window and global layers: its window layers')
        self._attn_window = getattr(mcfg, "sliding_window", None)
        if isinstance(self._attn_window, tuple):
            self._attn_window = min(
                (w for w in self._attn_window if w is not None), default=None)
        # bytes ONE gather-path step spends on the contiguous clone: k + v,
        # every layer, the full padded [B, T] view in the compute dtype
        # (an int8 pool dequantizes into the same-sized fp clone)
        # (nothing for a model that keeps no page: there is no K/V to clone)
        self._gather_bytes_step = 0 if self._pageless else (
            getattr(model, "num_layers", 0) * 2 * self.B * self.T
            * getattr(model, "num_kv_heads", 0) * getattr(model, "head_dim", 0)
            * jnp.dtype(cfg.kv_cache_dtype).itemsize)
        # request-lifecycle tracing (obs.tracing.Tracer or a per-replica
        # scope of one, None = off): the engine owns the per-request root
        # span and the COMPUTE phases (prefill incl. chunks, decode, spec
        # rounds, adapter acquire); the scheduler owns the WAIT phases
        # (queue, preempted park).  Every call site is guarded on `tracer
        # is not None` so the default path allocates nothing — the
        # zero-overhead-when-off contract tests assert via
        # obs.tracing.SPANS_CREATED.
        self.tracer = tracer
        self._rt: dict = {}       # rid -> {"root": Span, "phase": Span?}
        # fleet health monitor (obs.health.HealthMonitor, None = off;
        # falls back to the Observability hub's when one is attached):
        # evaluated on the step cadence over THIS registry, fed one SLO
        # event per terminal request.  Guarded at every call site so the
        # default path allocates nothing (ALERTS_EVALUATED discipline).
        if health is None and obs is not None:
            health = getattr(obs, "health_monitor", None)
        self._health = health
        if health is not None:
            health.attach_registry(self.registry)
        self.scheduler = SlotScheduler(
            self.B, self.C, self.T, max_queue=max_queue,
            page_gate=self._kv, reserve_extra=self._spec_k,
            max_batch_wait_s=max_batch_wait_s,
            shed_infeasible=shed_infeasible, tracer=tracer)
        self.step_timeout_s = step_timeout_s
        self._steps = 0
        self._t_step0 = 0.0   # the step's start on the engine's clock
        if transfer_guard not in ("off", "forbid"):
            raise ValueError(
                f"transfer_guard must be 'off' or 'forbid', "
                f"got {transfer_guard!r}")
        self._audit = TransferAudit(
            self.registry,
            mode="forbid" if transfer_guard == "forbid" else "observe")
        # decode programs launched and not yet fetched, oldest first: one
        # between steps, two between a step's dispatch and its collect (the
        # plain loop launches step N+1 before it reads step N's tokens)
        self._inflight: "deque[_InFlight]" = deque()
        # live weights (weights.WeightSwapper): the monotonic version of
        # the params currently serving (0 = process-start, never swapped).
        # An in-flight record keeps the version it was DISPATCHED under — a
        # swap between dispatch and collect must attribute the collected
        # tokens to the old version (the buffers that computed them)
        self.weights_version = 0
        # device mirror of the paged block tables (refreshed via the packed
        # explicit put only when admission/termination changes them)
        self._tables_dev = None
        # device mirrors of the per-slot sampling state, refreshed (one
        # explicit put each) only when admission changes the host copies
        self._sampling_dirty = True
        self._keys_dev = None
        self._temps_dev = None
        self._topks_dev = None
        self._topps_dev = None
        # compiled-cache evictions (trace._CompiledLRU) surface here too.
        # The caches live on the MODEL, which may outlive this engine or be
        # shared by several — attach only when nothing is attached yet, so
        # an existing registry (another live engine's, or one the caller set
        # explicitly) keeps receiving its counts.
        if getattr(model, "metrics_registry", None) is None:
            model.metrics_registry = self.registry
        self.eos_token_id = eos_token_id
        self._rng = rng
        self._clock = clock
        self._stats_path = stats_path
        self._stats_f = None

        # live device state: the global page pool (its HBM is num_pages *
        # page_bytes, decoupled from B * T) and the [B, T] key validity
        pool = model.make_page_pool(num_pages, page_size,
                                    quant=self._kv_quant)
        self.caches = pool.caches
        # the pool's page_bytes-derived logical size: what the memory
        # ledger accounts and what the fleet's headroom view is sized
        # from (pages_free * page_bytes)
        # (of the kinds the page gate counts: ``PagedKVManager.gating``)
        self._page_bytes = sum(
            pool.page_bytes_by_kind[k] for k in self._kv.gating)
        if self._recurrent:
            # what the recurrent layers' state rows hold of the device,
            # whatever the slots are doing
            self.registry.gauge("kvcache/state_bytes").set(pool.state_bytes)
        # what ONE token's K/V cells take of the device as it lays the
        # pool's arrays out (a 64-wide head alone in a 128-lane row reads
        # twice its bytes here, whatever a share of the pool's bytes says)
        self.registry.gauge("kvcache/page_bytes_per_token").set(
            pool.page_bytes_per_token)
        # what the host counts of each launch, by what the model's layers
        # are (models.hybrid.launch_counters); () for a model of attention
        # layers alone, whose launches then describe nothing
        self._counters = launch_counters(mcfg, self.registry,
                                         self._chunk_tokens)
        logger.info(
            "serving: paged KV pool: %s pages x %d tokens%s "
            "(%.1f MiB; [B=%d, T=%d] rows would be %.1f MiB)",
            pool.pages_by_kind, page_size,
            f" ({self._kv_quant} quantized)" if self._kv_quant else "",
            (pool.total_bytes - pool.state_bytes) / 2**20, self.B,
            self.T, sum(pool.page_bytes_by_kind) * self.B * self.T
            / page_size / 2**20)
        self.valid = jnp.zeros((self.B, self.T), jnp.int32)
        # the draft's KV state stays CONTIGUOUS [B, T]: its rollback is free
        # (rejected slots sit past the rewound offset, index-based causal
        # masking hides them, the next round overwrites them) so it needs no
        # page accounting — only the target's paged pool does
        if self._spec_k:
            self._draft_caches = draft.empty_caches()
            self._draft_valid = jnp.zeros((self.B, self.T), jnp.int32)
        self._offsets = np.full((self.B,), self.T, np.int32)  # T = parked
        self._next_tok = np.zeros((self.B,), np.int32)
        # per-slot occupancy generation, bumped at every admission: the
        # async collect uses it (with the slot-identity check) to discard
        # an in-flight token whose slot was released AND re-granted — even
        # back to the SAME request (preempt → requeue → re-admit inside one
        # step starts a fresh generation the stale token must never join)
        self._slot_gen = np.zeros((self.B,), np.int64)
        self._last_tok_time: List[Optional[float]] = [None] * self.B
        # per-slot sampling state, written once at admission so the decode
        # loop builds no per-slot keys host-side: base_keys[b] is the
        # request-stream key fold_in(rng, request_id) (zeros = greedy)
        self._base_keys = np.zeros((self.B, 2), np.uint32)
        self._temps = np.zeros((self.B,), np.float32)
        self._topks = np.zeros((self.B,), np.int32)
        self._topps = np.ones((self.B,), np.float32)

        # multi-adapter state (tenancy/): the preallocated device adapter
        # pool, the per-slot adapter page tables (all-NULL = adapter 0 =
        # exact identity), and the host-side slot -> adapter pin map the
        # terminal paths release through.  The table rides the packed
        # explicit put (async path) only when admission dirtied it.
        self._adapter_pool = None
        self._atables_dev = None
        if self._adapters is not None:
            if self._adapters.registry is None:
                self._adapters.attach_registry(self.registry)
            self._adapter_pool = model.make_adapter_pool(
                self._adapters.layout, self._adapters.num_pages)
            ap = self._adapters.layout.pages_per_adapter
            self._adapter_tables = np.zeros((self.B, ap), np.int32)
            self._slot_adapter = [0] * self.B
            self._adapter_dirty = True
        # spec × tenancy: when the draft shares the target's adapter
        # geometry (always true for a self-draft), its proposals run under
        # each slot's adapter too — sampled self-draft output stays
        # bit-identical to the plain adapter engine's.  A geometry-
        # incompatible draft proposes base-model tokens; the adapter-aware
        # verify still corrects the distribution, at a lower acceptance
        # rate.
        self._draft_lora = False
        if self._adapters is not None and draft is not None:
            from neuronx_distributed_tpu.tenancy.store import AdapterLayout

            lay = self._adapters.layout
            try:
                self._draft_lora = (
                    hasattr(draft, "prefill_one_lora")
                    and AdapterLayout.for_model(
                        draft, lay.rank, lay.page_elems) == lay)
            except (AttributeError, TypeError):
                self._draft_lora = False
            if self._draft_lora:
                draft._adapter_layout = lay
        if self._kv_quant is not None:
            self.registry.counter(QUANT_PAGES_TOTAL)

        # memory ledger: account every HBM subsystem this engine owns at
        # its LOGICAL size — the same page_bytes arithmetic the admission
        # gates use, so the mem/*_bytes gauges' sum IS the sizing model —
        # then take one device-truth poll where the backend supports it
        ml = self.memory_ledger
        if ml is not None:
            ml.account_tree("params", model.params)
            ml.set("kv_pool", pool.total_bytes - pool.state_bytes)
            if self._spec_k:
                from neuronx_distributed_tpu.obs.memory_ledger import (
                    tree_bytes,
                )

                ml.set("draft_kv", tree_bytes(self._draft_caches))
                ml.account_tree("draft_params", draft.params)
            if self._adapter_pool is not None:
                ml.set("adapter_pool",
                       int(getattr(self._adapter_pool, "nbytes", 0)))
            ml.poll_device()

        # pre-declare so a zero-request engine still exports the full set
        reg = self.registry
        reg.gauge("serving/queue_depth")
        reg.gauge("serving/slots_active")
        reg.histogram("serving/ttft_ms", MS_BUCKETS)
        reg.histogram("serving/intertoken_ms", MS_BUCKETS)
        reg.histogram("serving/step_ms", MS_BUCKETS)
        reg.histogram("serving/host_blocked_ms", MS_BUCKETS)
        reg.gauge("serving/last_step_ms")
        for c in ("admitted", "finished", "cancelled", "timed_out", "tokens",
                  "rejected", "failed", "slow_steps", "preemptions", "shed",
                  "expired_before_prefill", "prefill_chunks",
                  "decode_runahead", "decode_overrun_rows"):
            reg.counter(f"serving/{c}_total")
        for path in SAMPLER_PATHS:
            reg.counter(f"serving/sampler_steps_total/{path}")
        # per-priority-class latency histograms: the SLO story is per tier
        # (the whole point of priority scheduling is that the interactive
        # percentiles stay flat while batch absorbs the queueing)
        for cls in PRIORITIES:
            reg.histogram(f"serving/ttft_ms_{cls}", MS_BUCKETS)
            reg.histogram(f"serving/intertoken_ms_{cls}", MS_BUCKETS)
        if self._spec_k:
            # speculative throughput accounting: committed/rounds is the
            # tokens-per-step headline, accepted/proposed the draft quality
            for c in ("spec_proposed", "spec_accepted", "spec_committed",
                      "spec_rounds"):
                reg.counter(f"serving/{c}_total")

    # -- request surface ---------------------------------------------------

    def submit(self, request: Request) -> None:
        """Queue a request (FCFS).  Raises ``AdmissionError`` when it can
        never fit the compiled envelope, ``BackpressureError`` when the
        bounded admission queue is full (transient — retry after the backlog
        drains), ``ValueError`` for a sampled request on an rng-less
        engine."""
        if request.sampling.temperature > 0.0 and self._rng is None:
            raise ValueError(
                f"request {request.request_id} samples (temperature "
                f"{request.sampling.temperature}) but the engine has no rng")
        aid = getattr(request, "adapter_id", 0)
        if aid:
            # permanent rejections up front, like the envelope checks: an
            # unknown adapter can never be served, no matter the load
            if self._adapters is None:
                raise AdmissionError(
                    f"request {request.request_id} names adapter {aid} but "
                    "the engine has no adapter_store")
            if not self._adapters.registered(aid):
                raise AdmissionError(
                    f"request {request.request_id} names unregistered "
                    f"adapter {aid}")
        tr = self.tracer
        root = None
        if tr is not None:
            # the per-request root span (submit -> terminal emit); the
            # scheduler parents its queue span under it via _trace_root.
            # trace_id is what links the terminal serving_stats record to
            # this trace; a fleet requeue clone keeps the global id, and
            # its `hop` attr says which dispatch attempt these spans are.
            request.trace_id = request.request_id
            # every engine-side span is stamped from the ENGINE's clock
            # (injectable): mixed clocks would corrupt the trace whenever
            # a test or harness injects a fake clock
            root = tr.begin(
                "request", request_id=request.request_id,
                t=self._clock(),
                priority=request.priority, prompt_len=request.prompt_len,
                max_new_tokens=request.max_new_tokens,
                adapter_id=aid, hop=getattr(request, "hop", 0))
            request._trace_root = root
            self._rt[request.request_id] = {"root": root}
        try:
            self.scheduler.submit(request, now=self._clock())
        except SLOInfeasible:
            # distinct from queue-full backpressure: the deadline is already
            # dead under current load — shed at the edge, never admitted
            self.registry.counter("serving/shed_total").inc()
            if root is not None:
                self._rt.pop(request.request_id, None)
                tr.end(root, t=self._clock(), shed="slo_infeasible")
            raise
        except BackpressureError:
            self.registry.counter("serving/rejected_total").inc()
            if root is not None:
                self._rt.pop(request.request_id, None)
                tr.end(root, t=self._clock(), rejected="backpressure")
            raise
        except BaseException:
            if root is not None:
                self._rt.pop(request.request_id, None)
                tr.end(root, t=self._clock(), rejected="error")
            raise

    def cancel(self, request_id: int) -> bool:
        return self.scheduler.cancel(request_id)

    # -- disaggregation surface (fleet migration / fleet prefix cache) -----

    def withdraw(self, request_id: int) -> Request:
        """Pull a live request out of this engine WITHOUT a terminal
        output — the disaggregated fleet's migration hop.  Slot, page and
        adapter state are released exactly as a preemption park would be,
        but nothing is requeued and no stats record is written: the
        request continues on a sibling replica.  Its committed prompt
        chain survives through the prefix index's own references (the
        prefill's ``finish_insert`` registered it) — which is precisely
        the chain the migration exports.  Raises ``KeyError`` for ids
        this engine does not hold."""
        now = self._clock()
        # end the open compute phase BEFORE the scheduler forgets the
        # request (queued withdrawals have no phase; their queue span is
        # sealed by the scheduler itself)
        if self.scheduler.slot_of(request_id) is not None:
            rt = self._rt.get(request_id)
            if rt is not None and self.tracer is not None:
                self.tracer.end(rt.pop("phase", None), t=now, migrated=True)
        req, slot = self.scheduler.withdraw(request_id, now=now)
        if slot is not None:
            self._chunking.pop(slot, None)
            self._park_slot(slot)
        # a parked victim being migrated drops its local resume pin: the
        # destination resumes from the imported chain instead
        self._kv.release_resume(req)
        if self.tracer is not None:
            rt = self._rt.pop(request_id, None)
            if rt is not None:
                self.tracer.end(rt.get("root"), t=now, migrated=True,
                                new_tokens=len(req.generated))
        return req

    def export_prefix(self, fingerprint: int) -> Optional[ChainExport]:
        """Serialize the committed chain whose terminal fingerprint is
        ``fingerprint`` out of this engine's prefix index — the donor half
        of both KV migration and the fleet-global prefix cache.  Returns
        None when the index does not hold the chain (evicted since the
        directory last synced, or prefix caching off)."""
        self._refuse_migration()
        if self._kv.index is None:
            return None
        hit = self._kv.index.find_fingerprint(fingerprint)
        if hit is None:
            return None
        keys, pages, payload = hit
        return export_chain(self.caches, keys, pages,
                            page_size=self._kv.page_size, payload=payload,
                            registry=self.registry)

    def import_prefix(self, export: ChainExport) -> int:
        """Admit an exported chain into this engine's pool + prefix index
        — the receiver half.  Transactional (see
        :func:`~..kvcache.transfer.import_chain`: any failure, including a
        chaos kill at ``kvcache/page_import``, leaks nothing).  Returns
        the number of pages actually copied in (0 = already fully cached
        here)."""
        self._refuse_migration()
        if self._kv.index is None:
            raise TransferError(
                "engine has no prefix index; cannot import a chain")
        matched, _ = self._kv.index.peek(export.keys)
        already = sum(1 for p in matched if p != NULL_PAGE)
        self.caches = import_chain(self.caches, self._kv.index, export,
                                   registry=self.registry)
        return export.n_pages - already

    def _refuse_migration(self) -> None:
        why = self._cache_plan.refuses_migration(self._pages_freed)
        if why is not None:
            raise TransferError(why)

    @property
    def has_work(self) -> bool:
        # an in-flight decode is work: its results still need one more
        # step() to be collected and emitted
        return (self.scheduler.queue_depth > 0
                or self.scheduler.active_count > 0
                or bool(self._inflight))

    # -- engine loop -------------------------------------------------------

    def declare_warmup_done(self) -> None:
        """Everything this engine will run is compiled now: any compile the
        ledger sees from here on is a ``compile_storm`` (counted, flight-
        warned, traced).  Benches call this between their warm pass and the
        measured pass.  The step account starts over too (totals, longest
        step, trailing median): a warm-up's compiles are not stalls.  The
        first engine of a process to get here also declares the process
        ``ready`` (``obs.startup``): the steps so far were its ``warmup``,
        and the start-up totals land in this engine's registry."""
        started = startup.account()
        started.move("warmup", self.registry.counter(
            "serving/step_ms_total").value / 1e3)
        self._account.reset()
        if self.compile_ledger is not None:
            self.compile_ledger.declare_warmup_done("engine")
        started.ready("engine", self.registry)

    def install_params(self, params: Any, version: int) -> None:
        """Commit point of a live weight swap (``weights.WeightSwapper``):
        rebind the model's param pytree and bump the serving version.  The
        swapper has already validated + staged ``params`` against the
        compiled envelope, so every already-compiled phase program accepts
        the new pytree as a drop-in first argument — nothing recompiles
        (the compile ledger proves it).  The old buffers free by reference
        drop; a decode in flight that was dispatched against them keeps them
        alive exactly until its collect, and its tokens are attributed to
        the version its in-flight record carries (the one that computed
        them).

        Co-located replicas may SHARE one ``ParallelInferenceModel`` (one
        set of compiled phase fns, one param pytree) — a fleet mid-roll
        must not swap its neighbours, so the first install lazily replaces
        ``self.model`` with a shallow per-engine view: same compiled
        executables and caches by reference, private ``params`` binding."""
        model = self.model
        if not getattr(model, "_params_private", False):
            import copy

            view = copy.copy(model)
            view._params_private = True
            self.model = model = view
        model.params = params
        self.weights_version = int(version)
        # cached prefix KV (and full-hit prefill logits) embody the
        # OUTGOING params — a post-swap admission must never hit them, or
        # old-version output leaks past the version boundary
        dropped = self._kv.flush_prefix_cache()
        if dropped:
            logger.info("serving: weight swap flushed %d cached prefix "
                        "chain node(s)", dropped)
        ml = self.memory_ledger
        if ml is not None:
            # mem/params_bytes tracks the LIVE generation (the logical
            # sizing model; transiently both generations exist on device
            # until the old refs drop)
            ml.account_tree("params", params)

    def _poll_module_jits(self, led) -> None:
        """Book growth of the shared sampler jits' caches as compile events
        — the only visibility into recompiles of programs that live outside
        the per-model caches (wall time unknown: the compile happened
        inside jit dispatch)."""
        sizes = _module_jit_sizes()
        for name, n in sizes.items():
            if n > self._jit_sizes.get(name, 0):
                led.record_compile(f"jit:{name}", f"cache_size_{n}", None,
                                   kind="jit")
        self._jit_sizes = sizes
        # what dispatch compiled this step that neither a cache's first-call
        # timer nor the poll above saw (a placement-driven recompile of a
        # cached program) becomes a ``jit_dispatch`` storm
        led.reconcile()

    def step(self) -> List[RequestOutput]:
        """One engine iteration: sweep → admit → one prefill chunk → launch
        the next batched decode → collect the one before it (per-slot stop
        detection, slot free) → its stream callbacks and stats.  Returns
        the requests that reached a terminal state during this step; a
        request that stopped on a token did so one launch ago, and the row
        computed for it since is discarded.

        With a memory ledger attached, a RESOURCE_EXHAUSTED escaping the
        step dumps ``memory_breakdown.json`` naming the biggest holders
        before re-raising; with a compile ledger attached, the shared
        sampler jits' cache sizes are polled at the step's end (in its
        ``tail``).  Every step is bracketed by its own account
        (``obs.flight.StepAccount``: one flat record, the stall rule)."""
        # the loop's phases are spans of the profiler's own trace
        # (obs.tracing.phase: a flag test each when no profile is taken)
        # and entries of the step's account, through the one ``_phase``
        account = self._account
        account.begin(self._steps + 1)
        outputs: List[RequestOutput] = []
        try:
            with self._phase("step", step=self._steps + 1,
                             active=self.scheduler.active_count,
                             queued=self.scheduler.queue_depth):
                try:
                    outputs = self._step_impl()
                except Exception as e:
                    if self.memory_ledger is not None:
                        self.memory_ledger.oom_dump(e)
                    raise
                with self._phase("tail"):
                    self._step_tail()
        finally:
            account.end(self.scheduler.queue_depth,
                        self.scheduler.active_count, len(outputs),
                        self.has_work)
        return outputs

    def _phase(self, name: str, **attrs):
        """One phase of the step (a name of ``SERVE_PHASES``): the span
        ``nxd/serve/<name>`` in the profiler's trace and the phase's wall
        time in the step's account, at the same boundary."""
        return self._account.span(name, phase("serve/" + name, **attrs))

    def _step_impl(self) -> List[RequestOutput]:
        outputs: List[RequestOutput] = []
        now = self._clock()
        self._t_step0 = now
        self._steps += 1

        with self._phase("admit") as span:
            # 1) cancellation / deadline sweep (frees slots before admission)
            swept = self.scheduler.sweep(now)
            if swept:
                self._park_free_slots()
                for req in swept:
                    # a swept ACTIVE request still has its compute phase
                    # open (queued ones were closed by the scheduler's sweep)
                    self._trace_end_phase(req, t=now, swept=req.state.value)
                    self.registry.counter(
                        "serving/cancelled_total"
                        if req.state is RequestState.CANCELLED
                        else "serving/timed_out_total").inc()
                    outputs.append(self._emit(req, now))

            # 2) priority preemption: when the interactive head is blocked
            # on a full slot table (or exhausted pages), park batch-tier
            # victims — pages released transactionally, the request requeued
            # for a later token-identical re-prefill
            self._preempt_for_priority(now)

            # 3) admission: slot-insert prefill per granted request (its
            # device work queues behind the in-flight decode, keeping the
            # device busy while the host prepares the batch)
            granted = 0
            for slot, req in self.scheduler.admit(now):
                self._prefill_into_slot(slot, req, outputs)
                granted += 1
            span.set_metadata(granted=granted)
            self._account.granted = granted

        # 3b) prefill: advance the PREFILLING slots by the step's budget,
        # one chunk program (Sarathi-style — decodes below keep ticking
        # every step while long prompts trickle in)
        if self._chunking:
            self._run_prefill_chunks(outputs)

        # 4) decode, one step ahead: with step N in flight, launch step N+1
        # from N's tokens where they lie on the device, THEN collect N (one
        # explicit fetch, offsets committed, stop detection, slot release)
        # and run its host-side work (stream callbacks, telemetry, stats) —
        # all of it under the program just queued.  A speculative round
        # keeps collect-then-dispatch: its offsets are the accepted counts,
        # which only the fetch gives
        with self._audit.section("serving/decode"):
            if self._spec_k:
                with self._phase("collect"):
                    post = self._spec_collect()
                self._launch_decode()
            else:
                launched = self._launch_decode()
                with self._phase("collect"):
                    post = self._collect_decode(keep_newest=launched)
        with self._phase("finish", tokens=sum(
                len(p[3]) if p[0] == "tokens" else p[0] == "token"
                for p in post)):
            self._finish_decode(post, outputs)
        return outputs

    def _step_tail(self) -> None:
        """What a step does once its tokens are out (the span ``tail``; it
        launches nothing): the gauges — the pool's walk its free and
        evictable pages — the watchdog on the engine's clock, the health
        rules, and the compile ledger's poll of the shared sampler jits."""
        self.registry.gauge("serving/queue_depth").set(self.scheduler.queue_depth)
        self.registry.gauge("serving/slots_active").set(self.scheduler.active_count)
        if self._pages_freed:
            # window layers give back the pages every row that can still be
            # queried has moved past: a prefilling slot's next chunk starts
            # where its last one ended, a decoding slot's oldest row not yet
            # collected sits at its committed offset
            page = self._kv.page_size
            for slot, req in self.scheduler.active():
                st = self._chunking.get(slot)
                if st is not None:
                    oldest = st.fresh[st.next_i][0] * page
                elif req.state is RequestState.DECODE:
                    oldest = int(self._offsets[slot])
                else:
                    continue
                self._kv.release_behind(slot, min(oldest, self.T))
        self._kv.export_gauges()
        if self._adapters is not None:
            self._adapters.export_gauges()

        # step watchdog: a slow engine step is the host-side signature of a
        # recompile, a device stall, or a wedged model call — the gauge/
        # histogram make it graphable, the counter makes it alertable
        step_s = self._clock() - self._t_step0
        self.registry.gauge("serving/last_step_ms").set(step_s * 1e3)
        self.registry.histogram("serving/step_ms", MS_BUCKETS).observe(
            step_s * 1e3)
        if self.step_timeout_s is not None and step_s > self.step_timeout_s:
            self.registry.counter("serving/slow_steps_total").inc()
            logger.warning(
                "serving: engine step %d took %.3fs (> watchdog %.3fs; "
                "active=%d queued=%d)", self._steps, step_s,
                self.step_timeout_s, self.scheduler.active_count,
                self.scheduler.queue_depth)
        if self._health is not None:
            # rule evaluation rides the engine clock (alert edges share
            # the spans'/stats' timescale under a fake-clock harness)
            self._health.on_step(now=self._clock())
        if self.compile_ledger is not None:
            self._poll_module_jits(self.compile_ledger)

    def _row_live(self, slot: int, req: Request, gen: int) -> bool:
        """Whether a row of an in-flight program is still its request's:
        False once the request was swept (cancelled / timed out), stopped,
        or preempted AND re-admitted since the launch — the slot was
        released (and possibly re-granted), so the row's token is stale and
        its offset advance void.  The state check alone is not enough (a
        preemption round-trip can put the request back in DECODE within
        one step), and neither is slot identity (it can be re-granted the
        SAME slot) — the occupancy generation tells the generations
        apart."""
        return (req.state is RequestState.DECODE
                and self.scheduler.slot_of(req.request_id) == slot
                and self._slot_gen[slot] == gen)

    def _launch_decode(self) -> bool:
        """Decide the next decode's rows from what the host knows NOW and
        launch it; False when no slot has a token to compute.  A slot's
        rows still in flight (``ahead``: one, for a slot the step before
        also computed) count as done: its write offset and token index are
        advanced by them here — the collect commits them, or drops them
        with the stale token — and a request whose last token by LENGTH is
        already in flight gets no further row.  A stop token or a
        non-finite row shows only in the fetch, one step late: that row is
        an overrun (see :meth:`_collect_decode`)."""
        ahead = np.zeros((self.B,), np.int32)
        for rec in self._inflight:
            for slot, req, gen in rec.active:
                if self._row_live(slot, req, gen):
                    ahead[slot] += 1
        active = []
        for slot, req in self.scheduler.active():
            if req.state is not RequestState.DECODE:
                continue
            if len(req.generated) + ahead[slot] < req.max_new_tokens:
                active.append((slot, req))
            elif self._temps[slot] > 0.0:
                # its last token is in flight and it samples no other: its
                # row must not hold the batch on the sampler's sort
                # (:meth:`_park_slot` does this at the collect, a launch
                # too late)
                self._temps[slot] = 0.0
                self._sampling_dirty = True
        if not active:
            return False
        # parked (T: writes nothing) for every slot without a row
        offs = np.full((self.B,), self.T, np.int32)
        for slot, _ in active:
            offs[slot] = self._offsets[slot] + ahead[slot]
        self._count_paged_walk(active, offs)
        self._count_decode_write(active, offs)
        lens = np.asarray([int(offs[slot]) - self.C + req.prompt_len + 1
                           for slot, req in active])
        if self._pages_freed:
            for slot, _ in active:
                self._kv.extend_window(slot, int(offs[slot]))
        self._account.rows = len(active)
        visible = int(lens.sum())
        counted = self._count_launch(
            "decode_pages", lens - 1, lens, visible, len(active), self.B
        ) if self._counters else {}
        with self._phase("dispatch", active=len(active),
                         ctx_tokens=visible - len(active),
                         **({"state_rows": len(active)}
                            if self._recurrent else {}),
                         **self._window_tokens(lens, 1), **counted):
            if self._spec_k:
                self._spec_dispatch(active)
            else:
                self._dispatch_decode(active, offs, ahead)
        return True

    def _put_in_flight(self, packed, active: list, family: str, t_launch,
                       **kept) -> None:
        """Queue the program just launched for its collect, under what it
        was launched with: each row's occupancy generation, the weights
        version, the model's ``moe_seq``.  ``t_launch`` (None: tracing off,
        or a step is in flight ahead of it) opens its batch span."""
        rec = _InFlight(
            packed,
            [(slot, req, int(self._slot_gen[slot])) for slot, req in active],
            self.weights_version, getattr(self.model, "moe_seq", None),
            self._steps, family, **kept)
        if t_launch is not None:
            self._open_batch_span(rec, t_launch)
        self._inflight.append(rec)

    def _open_batch_span(self, rec: _InFlight, t0: float) -> None:
        """Open ``rec``'s batch-level span at ``t0``: its launch when the
        loop was idle, else the collect of the step before it — the honest
        device window of a program queued behind another; per-slot child
        spans land at collect time."""
        if self.tracer is not None:
            rec.span = self.tracer.begin(
                rec.family, t=t0, step=rec.step, active=len(rec.active),
                weights_version=rec.version,
                **({"k": self._spec_k} if self._spec_k else {}))

    def _close_batch_span(self, rec: _InFlight, now: float) -> None:
        """Seal the collected record's span at the fetch's return and open
        the next in-flight record's at the same instant."""
        if rec.span is not None:
            self.tracer.end(rec.span, t=now)
        if self._inflight and self.tracer is not None:
            self._open_batch_span(self._inflight[0], now)

    def dump_flight(self, reason: str) -> Optional[str]:
        """Persist the ring of step records (``flight_step`` documents at
        this moment, flat until then) where it has a file — an ``obs`` hub's
        ``flight_record.json``; the serving crash-evidence path used by
        ``replay_trace``."""
        return self._flight.dump(reason)

    def run_until_complete(self, max_steps: Optional[int] = None) -> List[RequestOutput]:
        """Drive ``step()`` until queue and slots drain; returns every
        terminal output in completion order."""
        outputs: List[RequestOutput] = []
        steps = 0
        while self.has_work:
            outputs.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"serving engine did not drain in {max_steps} steps "
                    f"(queue={self.scheduler.queue_depth}, "
                    f"active={self.scheduler.active_count})")
        return outputs

    def close(self) -> None:
        tr = self.tracer
        if tr is not None:
            # seal every open span (replica death / engine teardown): an
            # aborted span in the ring keeps the failover trace's pre-crash
            # coverage instead of losing it with the engine object
            now = self._clock()
            self.scheduler.trace_abort(now)
            for rec in self._inflight:
                if rec.span is not None:
                    tr.end(rec.span, t=now, aborted=True)
                    rec.span = None
            for rid, rt in list(self._rt.items()):
                tr.end(rt.pop("phase", None), t=now, aborted=True)
                tr.end(rt.get("root"), t=now, aborted=True)
            self._rt.clear()
        if self.memory_ledger is not None:
            try:
                self.memory_ledger.dump(reason="close")
            except OSError as e:  # teardown IO must not mask the exit path
                logger.warning("serving: memory breakdown dump failed: %s", e)
        if self._stats_f is not None:
            self._stats_f.close()
            self._stats_f = None

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    # -- internals ---------------------------------------------------------

    def _trace_begin_phase(self, req: Request, name: str,
                           t: Optional[float] = None, **attrs) -> None:
        """Open a compute-phase span (prefill / decode) under the request's
        root.  Phase boundaries reuse ONE timestamp (the grant instant, the
        first-token instant, the terminal instant), so a request's phases
        tile its lifetime exactly and the waterfall sums to its latency."""
        tr = self.tracer
        if tr is None:
            return
        rt = self._rt.get(req.request_id)
        if rt is None:
            return
        rt["phase"] = tr.begin(name, request_id=req.request_id,
                               parent=rt["root"], t=t, **attrs)

    def _trace_end_phase(self, req: Request, t: Optional[float] = None,
                         **attrs) -> None:
        tr = self.tracer
        if tr is None:
            return
        rt = self._rt.get(req.request_id)
        if rt is None:
            return
        tr.end(rt.pop("phase", None), t=t, **attrs)

    def _trace_phase_attrs(self, req: Request, **attrs) -> None:
        """Annotate the request's OPEN phase span (attrs merge at seal)."""
        if self.tracer is None:
            return
        rt = self._rt.get(req.request_id)
        if rt is not None and rt.get("phase") is not None:
            rt["phase"].attrs.update(attrs)

    def _trace_phase_of(self, req: Request):
        rt = self._rt.get(req.request_id) if self.tracer is not None else None
        return rt.get("phase") if rt is not None else None

    def _prefill_into_slot(self, slot: int, req: Request, outputs: list) -> None:
        """Admit one granted request into its slot: the adapter pin, the
        block table and its pages, the validity row, the draft's row.  Then
        either the first token at once — an exact full-prompt prefix hit
        hands back the cached prefill logits, no compute at all — or the
        hand-off to the chunk loop, which computes the fresh prompt pages a
        chunk a step while decodes keep ticking (the request stays
        PREFILLING until its last chunk lands).  A failure mid-admission
        reclaims every page and pin, fails the one request, and re-raises
        unless it was a transient pool exhaustion."""
        now = self._clock()
        # the prefill phase starts at the GRANT instant (where the queue /
        # preempted span ended), so the trace phases tile without gaps
        t_grant = req.prefill_time if req.prefill_time is not None else now
        # a preemption park ends at the grant: bank the parked wall time
        # (the serving_stats `preempted_ms` decomposition field)
        if req.parked_at is not None:
            req.preempted_ms += max(t_grant - req.parked_at, 0.0) * 1e3
            req.parked_at = None
        self._trace_begin_phase(req, "prefill", t=t_grant, slot=slot)
        # pre-dispatch expiry: the sweep ran at step start, but a request
        # can expire between sweep and prefill — never burn a prefill (or
        # its first chunk) on a deadline that is already dead
        if req.expired(now):
            self._expire_before_prefill(slot, req, outputs, now)
            return
        self._slot_gen[slot] += 1  # a fresh occupancy generation begins
        L = req.prompt_len
        ids = np.zeros((self.C,), np.int32)
        ids[self.C - L:] = req.prompt_ids  # LEFT-padded to the traced width
        valid = np.zeros((self.T,), np.int32)
        valid[self.C - L:self.C] = 1       # the prompt's keys; decode adds its own
        if not self._admit_adapter(slot, req, outputs):
            return
        cached = self._admit_pages(slot, req, ids, valid[:self.C], outputs)
        self.valid = self.model.insert_valid(self.valid, valid[None, :], slot)
        if self._spec_k:
            self._prefill_draft_row(slot, req, ids, valid)
        if cached is not None:
            # exact full-prompt prefix hit: the chain's pages already hold
            # this prompt's KV and the payload is the prefill's
            # last-position logits (keys are adapter-salted, so both were
            # computed under this same adapter)
            self._trace_phase_attrs(req, prefix_hit=True)
            with self._phase("first_token", request_id=req.request_id):
                self._finish_prefill(slot, req, jnp.asarray(cached), outputs,
                                     prefilled_fresh=False)
            return
        # EVERY fresh prompt rides the chunk loop: the block table is
        # assembled and the fresh pages reserved; the compute is the loop's,
        # one chunk program a step (a span that fits the budget completes
        # in this same step).  Fresh pages are always one contiguous
        # logical run (padding pages lead and ride the NULL page; the
        # matched prefix is a leading chain), so chunks walk it left to
        # right.
        fresh = self._kv.fresh_pages(slot)
        lps = [lp for lp, _ in fresh]
        assert lps == list(range(lps[0], lps[0] + len(lps))), (
            f"fresh prompt pages not contiguous: {lps}")
        self._chunking[slot] = _ChunkPrefill(req, ids, valid, fresh)
        # the prefill phase span stays OPEN across chunked steps; each
        # chunk adds a child span under it
        self._trace_phase_attrs(req, chunked=True, fresh_pages=len(fresh))

    def _admit_adapter(self, slot: int, req: Request, outputs: list) -> bool:
        """Pin the request's adapter at admission: its pages are taken (and
        device-loaded on a cold start) BEFORE any KV allocation, so the KV
        failure path has exactly one extra thing to undo.  False when the
        request failed here (emitted, slot freed): a transient adapter-pool
        exhaustion fails THIS request cleanly and the engine keeps serving;
        anything else re-raises after the same cleanup."""
        aid = req.adapter_id
        if not aid:
            return True
        tr = self.tracer
        aspan = (tr.begin("adapter_acquire", request_id=req.request_id,
                          parent=self._trace_phase_of(req),
                          t=self._clock(), adapter_id=aid)
                 if tr is not None else None)
        try:
            loads = self._adapters.acquire(aid, engine_step=self._steps)
            if aspan is not None:
                tr.end(aspan, t=self._clock(), loads=len(loads))
        except BaseException as e:
            now = self._clock()
            if aspan is not None:
                tr.end(aspan, t=now, failed=type(e).__name__)
            self._fail_slot_state(
                slot, req, now, reason=f"adapter:{type(e).__name__}")
            logger.warning(
                "serving: request %d failed acquiring adapter %d (%s) — "
                "slot %d freed", req.request_id, aid, e, slot)
            outputs.append(self._emit(req, now))
            if isinstance(e, PoolExhausted):
                return False
            raise
        for phys, block in loads:
            self._adapter_pool = self.model.write_adapter_page(
                self._adapter_pool, block, phys)
        return True

    def _admit_pages(self, slot: int, req: Request, ids, valid_ctx,
                     outputs: list):
        """Build the slot's block table: prefix lookup, then every page the
        request can need, atomically.  Returns the cached prefill logits on
        an exact full-prompt hit, else None.  A failure reclaims every page
        (and the admission's adapter pin), fails the one request, and
        re-raises."""
        aid = req.adapter_id
        try:
            cached = self._kv.admit_slot(slot, req, ids, valid_ctx,
                                         engine_step=self._steps)
        except BaseException as e:
            now = self._clock()
            if aid:
                self._adapters.release(aid)  # undo the admission pin
            self._fail_slot_state(slot, req, now,
                                  reason=f"page_alloc:{type(e).__name__}")
            logger.warning(
                "serving: request %d failed mid-page-allocation (%s) — "
                "every page reclaimed, slot %d freed", req.request_id,
                e, slot)
            outputs.append(self._emit(req, now))
            raise
        # the slot's lookup references now cover the resumable chain a
        # preemption park pinned (if any) — drop the park's pin so the
        # accounting returns to the one-holder-per-chain norm
        self._kv.release_resume(req)
        # from here the slot owns the adapter pin: every terminal path
        # releases it through _release_adapter
        if self._adapters is not None:
            self._slot_adapter[slot] = aid
            self._adapter_tables[slot] = self._adapters.table(aid)
            self._adapter_dirty = True
        return cached

    def _prefill_draft_row(self, slot: int, req: Request, ids, valid) -> None:
        """The DRAFT's prompt row (speculative serving).  The draft keeps a
        contiguous ``[B, T]`` cache row a slot — a rejected tail rolls back
        there for free — so its prompt prefills whole at admission
        (``prefill_one``: the small model's full-width forward is the cheap
        half) and lands by ``insert_slot``: the one caller of either.  It
        runs even on a target prefix hit (the draft's KV is not shared),
        sits parked (offset ``T``) until the target's last chunk lands, and
        is simply overwritten by the next insert if this admission fails."""
        ids_row = jnp.asarray(ids[None, :])
        valid_ctx = jnp.asarray(valid[None, :self.C])
        if self._draft_lora and req.adapter_id:
            _, row_caches = self._draft_model.prefill_one_lora(
                ids_row, valid_ctx, self._adapter_pool,
                self._adapter_tables[slot][None, :])
        else:
            _, row_caches = self._draft_model.prefill_one(ids_row, valid_ctx)
        self._draft_caches, self._draft_valid = self._draft_model.insert_slot(
            self._draft_caches, row_caches, self._draft_valid,
            valid[None, :], slot)

    def _set_sampling_state(self, slot: int, req: Request) -> None:
        """Write the slot's per-request sampler state (base key, temp,
        top-k/p) once, as its prefill ends, so the decode loop builds no
        per-slot keys host-side — and not before: while a slot is still
        chunking its row is not a decoding one, and ``_sample_rows`` picks
        the batch's work from every row (:meth:`_park_slot` is the other
        end)."""
        s = req.sampling
        if s.temperature > 0.0 and self._rng is not None:
            self._base_keys[slot] = np.asarray(
                request_rng(self._rng, req.request_id))
        else:
            self._base_keys[slot] = 0  # greedy: the sampler ignores the key
        self._temps[slot] = s.temperature
        self._topks[slot] = s.top_k
        self._topps[slot] = s.top_p
        self._sampling_dirty = True  # device mirrors refresh at next dispatch

    def _finish_prefill(self, slot: int, req: Request, logits,
                        outputs: list, prefilled_fresh: bool) -> None:
        """The prefill's first-token tail, shared by the exact-prefix-hit
        admission and the chunk loop's final chunk: sample, finite-gate,
        register the prefix chain, transition to DECODE, stream/emit."""
        s = req.sampling
        self._set_sampling_state(slot, req)
        toks, finite = _sample_rows(
            logits, jnp.asarray(self._base_keys[slot])[None, :],
            jnp.zeros((1,), jnp.int32),
            jnp.full((1,), s.temperature, jnp.float32),
            jnp.full((1,), s.top_k, jnp.int32),
            jnp.full((1,), s.top_p, jnp.float32))
        # admission is off the steady path, but its fetch is still ONE
        # explicit packed read (first token + finite flag together)
        first = self._fetch_tokens(_pack_tokens(toks, finite))
        now = self._clock()
        self.registry.counter("serving/admitted_total").inc()
        if not bool(first[1][0]):
            # quarantine BEFORE prefix-index registration: the pages and
            # logits of a poisoned prefill die with this request instead of
            # becoming a cached chain every identical prompt would replay
            self._fail_slot(slot, req, outputs, now)
            return
        if prefilled_fresh:
            # the payload is the DEVICE logits array (not a host copy): a
            # future full-prefix hit then feeds the sampler an input with
            # the same committed sharding as a fresh prefill's, instead of
            # recompiling it for an uncommitted host upload — a hit must
            # never cost a sampler compile mid-serve
            self._kv.finish_insert(slot, logits)
        tok = int(first[0][0])
        req.transition(RequestState.DECODE)
        # prefill ends and decode begins at the SAME first-token instant —
        # contiguous phases, so the waterfall sums to the request latency
        self._trace_end_phase(req, t=now)
        self._trace_begin_phase(req, "decode", t=now)
        # TTFT is a property of the REQUEST, not of this replica's
        # prefill: a migrated clone arrives with the source's first-token
        # instant already stamped (the user streamed their first token
        # there), so the re-prefill neither re-stamps nor re-observes it.
        # Preemption still re-stamps — reset_for_requeue nulls the field.
        if req.first_token_time is None:
            req.first_token_time = now
            if req.submit_time is not None:
                ttft_s = now - req.submit_time
                self.registry.histogram(
                    "serving/ttft_ms", MS_BUCKETS).observe(ttft_s * 1e3)
                self.registry.histogram(
                    f"serving/ttft_ms_{req.priority}", MS_BUCKETS).observe(
                        ttft_s * 1e3)
                # feed the deadline-feasibility estimator real service times
                self.scheduler.note_first_token(ttft_s)
        self._append_token(slot, req, tok, now)
        if not req.done:
            self._offsets[slot] = self.C
            self._next_tok[slot] = tok
        else:
            outputs.append(self._emit(req, now))

    def _run_prefill_chunks(self, outputs: list) -> None:
        """Advance every PREFILLING slot by up to the per-step chunk budget
        (``prefill_chunk_tokens``, in pages): each chunk scatters
        page-aligned prompt KV into the slot's reserved pages through
        ``prefill_chunk_pages``, and the FINAL chunk's last-position logits
        are the prefill logits the shared first-token tail samples from —
        token-identical whatever the chunk width.  Every chunk runs the
        ONE compiled program of the full budget width (a ragged tail is
        right-padded, see :meth:`_dispatch_chunk`), so a step dispatches
        ONE chunk, whatever that slot had left to prefill: prompt lengths
        never multiply compiled programs.  The start slot rotates step to
        step so one long prompt cannot hog the budget, and each slot's
        deadline is re-checked immediately before its dispatch (a dead
        request never burns a chunk)."""
        width = self._chunk_tokens // self._kv.page_size  # pages a chunk spans
        slots = sorted(self._chunking)
        start = self._chunk_rr % len(slots)
        self._chunk_rr += 1
        rotated = slots[start:] + slots[:start]
        # interactive prefills drink the budget first — a batch tier's long
        # prompt must not delay an interactive first token
        rotated.sort(
            key=lambda s: self._chunking[s].req.priority
            != PRIORITY_INTERACTIVE)
        for slot in rotated:
            st = self._chunking.get(slot)
            if st is None:
                continue
            req = st.req
            now = self._clock()
            if req.expired(now):
                # pre-dispatch expiry: the head died mid-chunking — reclaim
                # its pages now instead of finishing a prefill nobody reads
                self._chunking.pop(slot, None)
                self._expire_before_prefill(slot, req, outputs, now)
                continue
            n_pages = min(width, st.pages_remaining)
            page = self._kv.page_size
            off = st.fresh[st.next_i][0] * page
            try:
                # ctx_tokens: the keys the chunk's last row attends — its
                # end in the left-padded row less the pad
                ctx = off + n_pages * page - (self.C - req.prompt_len)
                # the chunk's own tokens: its rows less a first page's pads
                chunk_tokens = ctx - max(off - (self.C - req.prompt_len), 0)
                self._account.chunk = n_pages * page
                counted = self._count_launch(
                    "prefill_chunk_pages",
                    np.arange(ctx - chunk_tokens, ctx), req.prompt_len,
                    ctx, chunk_tokens, n_pages * page
                ) if self._counters else {}
                with self._phase(
                        "prefill_chunk", request_id=req.request_id,
                        tok_start=off, width=n_pages * page, ctx_tokens=ctx,
                        chunk_tokens=chunk_tokens,
                        **self._window_tokens([ctx], n_pages * page),
                        **counted):
                    self._dispatch_chunk(slot, st, n_pages)
            except BaseException as e:
                # transactional like the admission path: the one request
                # fails, every page is reclaimed, then the fault propagates
                # (a fleet replica treats it as a crash and requeues)
                now = self._clock()
                self._chunking.pop(slot, None)
                self._fail_slot_state(
                    slot, req, now,
                    reason=f"prefill_chunk:{type(e).__name__}")
                logger.warning(
                    "serving: request %d failed mid-chunked-prefill (%s) — "
                    "every page reclaimed, slot %d freed", req.request_id,
                    e, slot)
                outputs.append(self._emit(req, now))
                raise
            if st.pages_remaining == 0:
                self._chunking.pop(slot, None)
                with self._phase("first_token", request_id=req.request_id):
                    self._finish_prefill(slot, req, st.logits, outputs,
                                         prefilled_fresh=True)
            break  # the step's budget is one chunk program

    def _dispatch_chunk(self, slot: int, st: _ChunkPrefill,
                        n_pages: int) -> None:
        """One ``prefill_chunk_pages`` call covering the slot's next
        ``n_pages`` fresh prompt pages (page-aligned, contiguous).  The
        program is always ``prefill_chunk_tokens`` wide: a shorter span (a
        prompt's tail) is right-padded, and the logits are read at the
        span's last row.  The pad rows lie past the prompt's end — invalid
        cells, which the model's scatter never commits.  Only the prompt's
        LAST chunk asks for logits (one row of the head); the others ask
        for none and ``st.logits`` stays unset."""
        page = self._kv.page_size
        off = st.fresh[st.next_i][0] * page
        width = n_pages * page
        last = n_pages == st.pages_remaining
        ids_chunk = np.zeros((1, self._chunk_tokens), np.int32)
        ids_chunk[0, :width] = st.ids_row[off:off + width]
        if self._pages_freed:
            self._kv.extend_window(slot, off + width - 1)
        # the chunk commits its valid cells (a first page may lead with pads)
        cells = st.valid_row[off:off + width].reshape(n_pages, page) > 0
        self._count_kv_write(int(cells.sum()), int(cells.any(axis=1).sum()))
        tr = self.tracer
        cspan = (tr.begin("prefill_chunk", request_id=st.req.request_id,
                          parent=self._trace_phase_of(st.req),
                          t=self._clock(),
                          tok_start=int(off), tok_end=int(off + width),
                          pages=n_pages)
                 if tr is not None else None)
        # chaos hook: a kill mid-chunked-prefill must reclaim every page
        # and leave the request cleanly requeue-able (tests/test_slo_*)
        try:
            fault_point("serving/prefill_chunk",
                        request_id=st.req.request_id,
                        engine_step=self._steps, chunk_offset=off)
            # an adapter request's chunks prefill with its LoRA deltas
            # applied (all-NULL tables = adapter 0 = exact base model)
            ad = ((self._adapter_pool, self._adapter_tables[slot][None, :])
                  if self._adapters is not None else (None, None))
            logits, self.caches = self.model.prefill_chunk_pages(
                jnp.asarray(ids_chunk), off,
                self._kv.tables[..., slot, :][..., None, :].copy(),
                self.caches,
                st.valid_row[None, :].copy(), apool=ad[0], atables=ad[1],
                paged_kernel=self._paged_kernel, last_row=width - 1,
                want_logits=last,
                **({"state_row": slot} if self._recurrent else {}))
        except BaseException as e:
            if cspan is not None:
                tr.end(cspan, t=self._clock(), failed=type(e).__name__)
            raise
        if cspan is not None:
            tr.end(cspan, t=self._clock())
        st.req.prefill_chunks += 1
        st.next_i += n_pages
        if not self._paged_kernel:
            # gather-path chunk: it attends a per-row [1, T] clone of the
            # committed pool — book its rematerialized bytes honestly so
            # the `gather_bytes_total == 0` kernel-mode gate covers chunked
            # prefill too (with the kernel on, the chunk walks the pool
            # in-kernel and this counter must NOT move)
            self.registry.counter(GATHER_BYTES_TOTAL).inc(
                self._gather_bytes_step // self.B)
        if self._kv_quant is not None:
            # the chunk's page-aligned writes each requantized their page
            self.registry.counter(QUANT_PAGES_TOTAL).inc(n_pages)
        if last:
            # the prefill logits the first token will sample from
            st.logits = perturb("serving/prefill_logits", logits,
                                request_id=st.req.request_id,
                                engine_step=self._steps)
        self.registry.counter("serving/prefill_chunks_total").inc()
        self.registry.counter(
            "serving/head_rows_total/prefill_chunk_pages").inc(int(last))

    def _preempt_for_priority(self, now: float) -> None:
        """Park batch-tier victims while the scheduler says the interactive
        head is blocked on slots/pages: pages released transactionally, the
        victim requeued at its original EDF position for a later
        token-identical re-prefill (the clone discipline the fleet's
        failover already proved)."""
        for _ in range(self.B):
            picked = self.scheduler.pick_preemption(now)
            if picked is None:
                return
            slot, req = picked
            # the active compute phase ends at the park instant; the
            # scheduler opens the "preempted" gap span at the same `now`
            self._trace_end_phase(req, t=now, preempted=True)
            self.scheduler.requeue(req, now=now)  # frees slot, resets req
            req.parked_at = now
            st = self._chunking.pop(slot, None)
            # pin the victim's COMMITTED leading chain before the slot's
            # references drop: the re-grant then matches it in the prefix
            # index and re-prefills only the uncommitted tail (a DECODE
            # victim skips prefill entirely).  A mid-chunk victim's
            # committed depth is its chunk progress.
            self._kv.park_resume(
                slot, req, fresh_done=st.next_i if st is not None else None)
            self._park_slot(slot)
            self.registry.counter("serving/preemptions_total").inc()
            logger.info(
                "serving: preempted batch request %d from slot %d for the "
                "interactive queue head (%d preemption(s) so far)",
                req.request_id, slot, req.preemptions)

    def _expire_before_prefill(self, slot: int, req: Request, outputs: list,
                               now: float) -> None:
        """A granted request whose deadline expired between the step-start
        sweep and its prefill (or next chunk) dispatch: terminal TIMED_OUT
        without burning any prefill compute, slot and pages reclaimed."""
        req.transition(RequestState.TIMED_OUT)
        req.finish_reason = RequestState.TIMED_OUT.value
        req.finish_time = now
        req.shed_reason = SHED_EXPIRED_BEFORE_PREFILL
        self._trace_end_phase(req, t=now, expired=True)
        self.scheduler.release(req)
        self._park_slot(slot)
        self.registry.counter("serving/expired_before_prefill_total").inc()
        self.registry.counter("serving/timed_out_total").inc()
        outputs.append(self._emit(req, now))

    def _window_tokens(self, lens, rows: int) -> dict:
        """The span's ``window_tokens``: the keys the coming program's
        WINDOW layers attend, where ``ctx_tokens`` is what its global layers
        do — each row's keys capped at the window (a chunk of ``rows`` rows:
        its last row's, and the ``rows - 1`` its first row sees before
        them).  Empty for a model without a window."""
        if self._attn_window is None:
            return {}
        cap = self._attn_window + rows - 1
        return {"window_tokens": int(sum(
            min(int(n), cap) for n in lens)) - (len(lens) if rows == 1 else 0)}

    def _count_paged_walk(self, active: list, offs) -> None:
        """What the traffic lets the paged kernel skip, from the write
        offsets ``offs`` the coming decode is launched at (the dispatch
        span's ``ctx_tokens`` sums each less its pad: the keys the paged
        kernel must read): the pages the coming decode's live
        slots attend (``serving/paged_pages_walked_total`` — each slot's
        band ``[max(pad, offset - window + 1), offset + rows - 1]`` in
        pages; a hybrid model's global layers walk the whole band, this is
        its windowed layers') beside the pages its block tables could hold
        (``serving/paged_pages_tabled_total``: slots x pages a slot)."""
        if not self._paged_kernel or self._pageless:
            return
        page = self._kv.page_size
        rows = self._spec_k + 1 if self._spec_k else 1
        walked = 0
        for slot, req in active:
            off = int(offs[slot])
            low = self.C - req.prompt_len
            if self._attn_window is not None:
                low = max(low, off - self._attn_window + 1)
            walked += (off + rows - 1) // page - low // page + 1
        self.registry.counter("serving/paged_pages_walked_total").inc(walked)
        self.registry.counter("serving/paged_pages_tabled_total").inc(
            self.B * self._kv.pages_per_slot)

    def _count_launch(self, family: str, positions, lengths, visible: int,
                      rows: int, width: int) -> dict:
        """Describe the coming paged program ONCE (``models.hybrid.Launch``)
        to each of the model's launch counters; the span keys they return
        (``selected_tokens``).  Called only where the model has any."""
        launch = Launch(family, positions, lengths, visible, rows, width)
        keys: dict = {}
        for count in self._counters:
            keys.update(count(launch) or ())
        return keys

    def _count_kv_write(self, rows: int, pages: int) -> None:
        """What the coming program commits to the page pool, a layer: the
        K/V rows (``serving/kv_rows_written_total``) and the pool pages they
        land in (``serving/kv_pages_touched_total`` — what the page-granular
        writer, ``ops.kv_pool_write``, reads and writes back)."""
        if self._pageless:
            return      # no layer keeps a page: nothing is committed to one
        self.registry.counter("serving/kv_rows_written_total").inc(rows)
        self.registry.counter("serving/kv_pages_touched_total").inc(pages)

    def _count_decode_write(self, active: list, offs) -> None:
        """The coming decode's (or verify round's) pool write, from the
        offsets it is launched at: every live slot's row(s) from its offset
        on — a verify chunk's rows past the table's end are dropped."""
        page = self._kv.page_size
        rows = pages = 0
        for slot, _ in active:
            off = int(offs[slot])
            last = min(off + self._spec_k + 1, self.T) - 1
            if last >= off:
                rows += last - off + 1
                pages += last // page - off // page + 1
        self._count_kv_write(rows, pages)

    def _count_gather_step(self) -> None:
        """Account one gather-path paged step's ``[B, T]`` K/V
        rematerialization; the block-table-native kernel path never calls
        this, so ``kvcache/gather_bytes_total`` staying flat IS the
        "attend in HBM" evidence the report's kv-cache line shows."""
        if not self._paged_kernel:
            self.registry.counter(GATHER_BYTES_TOTAL).inc(
                self._gather_bytes_step)

    def _fetch_tokens(self, packed_dev, upto=None):
        """The step's ONE device->host read.  A routed model's expert loads
        (``[L, E]`` a paged program, waiting on the device since their
        program ran) ride it, so counting them costs no sync of its own:
        ``upto`` (a decode's ``moe_seq``) leaves the loads of programs
        launched AFTER the one whose tokens are read — this step's prefill
        chunk — for the next fetch, which would otherwise wait for them."""
        self._account.fetches += 1
        book = self._moe_book
        programs, loads = book.take(upto) if book is not None else ((), ())
        with self._phase("fetch"):
            if not loads:
                return self._audit.fetch(packed_dev, label="serving")
            packed, loads = self._audit.fetch((packed_dev, loads),
                                              label="serving")
        book.book(programs, loads)
        return packed

    def _count_sampler_step(self) -> None:
        """Book which branch of ``_sample_rows`` the coming decode takes,
        from the host mirrors of the vectors it is handed (the same
        formula; no device read)."""
        path = SAMPLER_PATHS[int(_sampler_path(self._temps, self._topks,
                                               self._topps))]
        self.registry.counter(f"serving/sampler_steps_total/{path}").inc()

    def _collect_decode(self, keep_newest: bool = False) -> list:
        """Collect the OLDEST decode step in flight: ONE explicit packed
        fetch (tokens + finite flags), then the cheap bookkeeping — the
        offset advance its launch took on credit is committed, non-finite
        quarantine, stop detection, slot release.  ``keep_newest``: this
        step launched a program, which stays in flight (reading it now
        would idle the device for the host's next turn) — so nothing is
        collected when it is the only one.  Returns the deferred host work
        as ``(kind, slot, req, tok, intertoken_ms, now)`` records for
        :meth:`_finish_decode`.

        The step after this one may already be queued, launched when the
        host knew only counts: a request that stops here on a TOKEN
        (``stop_token_ids``, ``eos_token_id``) or goes non-finite has a row
        in it — an **overrun** (``serving/decode_overrun_rows_total``).
        That row's token is never read (the request is no longer live when
        its step is collected: :meth:`_row_live`), its offset advance is
        dropped, and its write landed past the request's last cell in a
        decode page the slot held when the program was queued; the slot is
        released here, whatever is admitted into it is launched later on
        the same device queue, and the prefix index holds prompt pages
        only, so nothing an overrun writes is ever read."""
        if len(self._inflight) <= (1 if keep_newest else 0):
            return []
        rec = self._inflight.popleft()
        packed = self._fetch_tokens(rec.packed, rec.moe_seq)  # [2, B]
        toks, finite = packed[0], packed[1]
        now = self._clock()
        tr = self.tracer
        bspan = rec.span
        queued = self._inflight[0].active if self._inflight else ()
        post: list = []
        for slot, req, gen in rec.active:
            if not self._row_live(slot, req, gen):
                # swept, stopped, or preempted and re-admitted while the
                # step was in flight: the stale token is discarded and the
                # offset untouched
                continue
            self._offsets[slot] += 1  # the step wrote req's previous token
            if not finite[slot]:
                self._fail_slot_state(slot, req, now)
                self._count_overrun(slot, gen, queued)
                post.append(("fail", slot, req, 0, None, now))
                continue
            tok = int(toks[slot])
            last = self._last_tok_time[slot]
            ms = (now - last) * 1e3 if last is not None else None
            req.generated.append(tok)
            # attributed to the version that DISPATCHED this step — a swap
            # between dispatch and collect computed under the old buffers
            req.weights_version = rec.version
            req.decode_steps += 1
            if bspan is not None:
                tr.instant("decode_slot", request_id=req.request_id,
                           parent=bspan, t=now, slot=slot,
                           tok_idx=len(req.generated) - 1)
            self._last_tok_time[slot] = now
            self.registry.counter("serving/tokens_total").inc()
            reason = self._stop_reason(req, tok)
            if reason is not None:
                self._finish_request(slot, req, reason, now)
                self._count_overrun(slot, gen, queued)
            else:
                self._next_tok[slot] = tok
            post.append(("token", slot, req, tok, ms, now))
        self._close_batch_span(rec, now)
        return post

    def _count_overrun(self, slot: int, gen: int, queued) -> None:
        """A request just ended at a collect: count the row the step
        already queued (``queued``: its rows) computed for it, if any — none
        for a stop by length, whose last token the launch could count."""
        if any(s == slot and g == gen for s, _, g in queued):
            self.registry.counter("serving/decode_overrun_rows_total").inc()

    def _dispatch_decode(self, active: list, offs, ahead) -> None:
        """Dispatch one per-slot-offset decode + row-wise sampling for
        ``active`` and leave the packed result in flight — BEHIND the step
        before it, when that one has not been fetched yet
        (``serving/decode_runahead_total``).  ``offs [B]`` are the write
        offsets and ``ahead [B]`` each slot's rows still in flight
        (:meth:`_launch_decode`); the token index is the request's count
        plus those rows, so the sampler keys are what a fetched count would
        give.  A slot with a row in flight is fed that row's sampled token
        where it lies on the device; the others — they began decoding since
        — the host's ``_next_tok`` (:func:`_feed_tokens`).

        All host→device traffic is explicit: the per-step-varying inputs
        (next-token feed, write offsets, token indices, the feed's mask)
        stage as ONE explicit pytree put; the admission-time sampling state
        rides device mirrors refreshed only when dirty.  Host arrays are
        copied before staging — on backends where ``device_put`` aliases
        host memory, the engine's in-place mutation of ``_next_tok`` must
        never reach into an in-flight computation."""
        tok_idx = np.zeros((self.B,), np.int32)
        for slot, req in active:
            tok_idx[slot] = len(req.generated) + ahead[slot]
        before = self._inflight[-1] if self._inflight else None
        t_launch = (self._clock() if before is None
                    and self.tracer is not None else None)
        # eager slicing of a stacked [3, B] array would bind scalar start
        # indices host-side (an implicit transfer the guard rejects), so the
        # per-step inputs stage as one explicit pytree put instead; a dirty
        # block table rides the SAME put (still one explicit host→device
        # crossing per step) and a clean one reuses its mirror
        staged = [self._next_tok[:, None].copy(), offs, tok_idx]
        if before is not None:
            staged.append(ahead == 0)
        stage_kv = self._kv.tables_dirty or self._tables_dev is None
        stage_ad = self._adapters is not None and (
            self._adapter_dirty or self._atables_dev is None)
        if stage_kv:
            staged.append(self._kv.tables.copy())
        if stage_ad:
            # a dirty adapter table rides the SAME packed put as the block
            # tables — still one explicit host→device crossing per step
            staged.append(self._adapter_tables.copy())
        put = list(self._audit.put(tuple(staged)))
        tok, offs_dev, tidx = put[:3]
        cursor = 3
        if before is not None:
            tok = _feed_tokens(before.toks, tok, put[cursor])
            cursor += 1
            self.registry.counter("serving/decode_runahead_total").inc()
        if stage_kv:
            self._tables_dev = put[cursor]
            cursor += 1
            self._kv.tables_dirty = False
        if stage_ad:
            self._atables_dev = put[cursor]
            cursor += 1
            self._adapter_dirty = False
        if self._adapters is not None:
            logits, self.caches, self.valid = self.model.decode_pages_lora(
                tok, offs_dev, self._tables_dev, self.caches, self.valid,
                self._adapter_pool, self._atables_dev,
                paged_kernel=self._paged_kernel)
        else:
            logits, self.caches, self.valid = self.model.decode_pages(
                tok, offs_dev, self._tables_dev, self.caches, self.valid,
                paged_kernel=self._paged_kernel)
        self._count_gather_step()
        self.registry.counter(
            "serving/head_rows_total/decode_pages").inc(self.B)
        if self._kv_quant is not None:
            # every active slot's decode write requantized its page
            self.registry.counter(QUANT_PAGES_TOTAL).inc(len(active))
        logits = perturb("serving/decode_logits", logits,
                         engine_step=self._steps)
        if self._sampling_dirty:
            self._keys_dev, self._temps_dev, self._topks_dev, \
                self._topps_dev = self._audit.put(
                    (self._base_keys.copy(), self._temps.copy(),
                     self._topks.copy(), self._topps.copy()))
            self._sampling_dirty = False
        self._count_sampler_step()
        toks, finite = _sample_rows(
            logits, self._keys_dev, tidx,
            self._temps_dev, self._topks_dev, self._topps_dev)
        self._put_in_flight(_pack_tokens(toks, finite), active,
                            "decode_step", t_launch, toks=toks)

    def _spec_dispatch(self, active: list) -> None:
        """Dispatch one speculative draft-k-verify round for the current
        active set and leave the packed ``[k+3, B]`` result in flight.

        The draft proposes ``k`` tokens per slot (k batched single-token
        decodes on its contiguous caches, sampling from the same
        per-request streams as the plain engine), the target scores the
        whole ``[B, k+1]`` chunk in ONE ``verify_pages`` call that also
        scatters the chunk into the paged pool, and the accept/commit math
        runs on device (:func:`_spec_accept`) so the round's device→host
        traffic stays ONE packed fetch — the ``[2, B]`` single-token payload
        widened to ``[k+3, B]``.  All per-round host→device traffic stages
        as the same ONE packed explicit put as the plain path (per-step
        draft offsets and token indices are precomputed host-side as
        ``[k, B]`` arrays — no eager scalar arithmetic for the transfer
        guard to reject)."""
        k = self._spec_k
        tok_idx = np.zeros((self.B,), np.int32)
        for slot, req in active:
            tok_idx[slot] = len(req.generated)
        t_launch = self._clock() if self.tracer is not None else None
        offs_steps = self._offsets[None, :] + np.arange(k, dtype=np.int32)[:, None]
        tidx_steps = tok_idx[None, :] + np.arange(k, dtype=np.int32)[:, None]
        staged = [self._next_tok[:, None].copy(), self._offsets.copy(),
                  tok_idx, offs_steps, tidx_steps]
        stage_kv = self._kv.tables_dirty or self._tables_dev is None
        stage_ad = self._adapters is not None and (
            self._adapter_dirty or self._atables_dev is None)
        if stage_kv:
            staged.append(self._kv.tables.copy())
        if stage_ad:
            # a dirty adapter table rides the SAME packed put as the block
            # tables — still one explicit host→device crossing per round
            staged.append(self._adapter_tables.copy())
        put = list(self._audit.put(tuple(staged)))
        tok, offs, tidx, offs_j, tidx_j = put[:5]
        cursor = 5
        if stage_kv:
            self._tables_dev = put[cursor]
            cursor += 1
            self._kv.tables_dirty = False
        if stage_ad:
            self._atables_dev = put[cursor]
            cursor += 1
            self._adapter_dirty = False
        if self._sampling_dirty:
            self._keys_dev, self._temps_dev, self._topks_dev, \
                self._topps_dev = self._audit.put(
                    (self._base_keys.copy(), self._temps.copy(),
                     self._topks.copy(), self._topps.copy()))
            self._sampling_dirty = False
        draft = self._draft_model
        dtok = tok
        props, q_filts, dfin = [], [], None
        # an adapter-compatible draft proposes under each slot's adapter
        # (the same gathered-delta path as the target's verify), so with
        # draft == target the proposals ARE the plain adapter engine's draws
        dad = ((self._adapter_pool, self._atables_dev)
               if self._draft_lora else (None, None))
        for j in range(k):
            dlogits, self._draft_caches, self._draft_valid = \
                draft.decode_slots(dtok, offs_j[j], self._draft_caches,
                                   self._draft_valid, apool=dad[0],
                                   atables=dad[1])
            dlogits = perturb("serving/draft_logits", dlogits,
                              engine_step=self._steps, round_pos=j)
            ptoks, qf, fin = _propose_rows(
                dlogits, self._keys_dev, tidx_j[j], self._temps_dev,
                self._topks_dev, self._topps_dev)
            props.append(ptoks)
            q_filts.append(qf)
            dfin = fin if dfin is None else jnp.logical_and(dfin, fin)
            dtok = ptoks[:, None]
        chunk = jnp.concatenate([tok] + [t[:, None] for t in props], axis=1)
        # adapter-aware verify (spec × tenancy): the chunk is scored under
        # each slot's OWN adapter — the same gathered-delta path its plain
        # decode would take — so acceptance judges the distribution the
        # request actually samples from
        ad = ((self._adapter_pool, self._atables_dev)
              if self._adapters is not None else (None, None))
        vlogits, self.caches, self.valid = self.model.verify_pages(
            chunk, offs, self._tables_dev, self.caches, self.valid,
            apool=ad[0], atables=ad[1], paged_kernel=self._paged_kernel)
        self._count_gather_step()
        self.registry.counter(
            "serving/head_rows_total/verify_pages").inc(self.B * (k + 1))
        if self._kv_quant is not None:
            # every active slot's k+1-token verify write requantized the
            # page(s) its chunk straddles — book them honestly
            page = self._kv.page_size
            pages = sum(
                int((self._offsets[slot] + k) // page
                    - self._offsets[slot] // page + 1)
                for slot, _ in active)
            self.registry.counter(QUANT_PAGES_TOTAL).inc(pages)
        vlogits = perturb("serving/verify_logits", vlogits,
                          engine_step=self._steps)
        packed = _spec_accept(
            vlogits, jnp.stack(q_filts, axis=1), jnp.stack(props, axis=1),
            self._keys_dev, tidx, self._temps_dev, self._topks_dev,
            self._topps_dev, dfin)
        self._put_in_flight(packed, active, "spec_round", t_launch,
                            last_prop=props[-1])

    def _spec_collect(self) -> list:
        """Collect the in-flight speculative round: ONE explicit packed
        fetch, then per-slot commit — append the accepted run (clipped to
        the request's remaining budget and cut at the first stop token),
        advance the slot's write offset by exactly the committed length,
        quarantine non-finite slots, and dispatch the draft catch-up write
        for fully-accepted slots (the one proposal the draft sampled but
        never wrote).

        The offset rewind IS the rollback of a rejected tail: the verify
        step wrote ``k+1`` tokens but only ``m`` stay committed; the tail
        past ``offset + m`` sits in pages reserved at admission (pure
        host-side accounting, no device copy), index-based causal masking
        hides its stale keys, and later rounds overwrite them before any
        query can attend that far."""
        if not self._inflight:
            return []
        rec = self._inflight.popleft()
        active, last_prop = rec.active, rec.last_prop
        k = self._spec_k
        packed = self._fetch_tokens(rec.packed, rec.moe_seq)  # [k+3, B]
        commit, acc, finite = packed[:k + 1], packed[k + 1], packed[k + 2]
        now = self._clock()
        tr = self.tracer
        bspan = rec.span
        post: list = []
        ingest = np.full((self.B,), self.T, np.int32)
        need_ingest = False
        reg = self.registry
        for slot, req, gen in active:
            if not self._row_live(slot, req, gen):
                # swept — or preempted and re-admitted — while the round
                # was in flight
                continue
            if not finite[slot]:
                self._fail_slot_state(slot, req, now)
                post.append(("fail", slot, req, 0, None, now))
                continue
            a = int(acc[slot])
            req.spec_proposed += k
            req.spec_accepted += a
            reg.counter("serving/spec_proposed_total").inc(k)
            reg.counter("serving/spec_accepted_total").inc(a)
            reg.counter("serving/spec_rounds_total").inc()
            rem = req.max_new_tokens - len(req.generated)
            plan = min(a + 1, rem)
            last = self._last_tok_time[slot]
            gap_ms = (now - last) * 1e3 if last is not None else None
            toks: list = []
            reason = None
            for i in range(plan):
                t = int(commit[i, slot])
                req.generated.append(t)
                toks.append(t)
                reg.counter("serving/tokens_total").inc()
                reason = self._stop_reason(req, t)
                if reason is not None:
                    break  # stop inside the accepted run: commit up to it
            m = len(toks)
            reg.counter("serving/spec_committed_total").inc(m)
            if m:
                # the round ran under the dispatching version's buffers
                req.weights_version = rec.version
            req.decode_steps += 1
            if bspan is not None:
                # per-slot round outcome: proposals accepted + tokens
                # committed (the accepted-run length the k-sweep tunes)
                tr.instant("spec_slot", request_id=req.request_id,
                           parent=bspan, t=now, slot=slot, accepted=a,
                           committed=m)
            self._offsets[slot] += m
            self._last_tok_time[slot] = now
            if reason is not None:
                self._finish_request(slot, req, reason, now)
            else:
                self._next_tok[slot] = toks[-1]
                if m == k + 1:
                    # full accept, still decoding: the draft's own cache
                    # never ingested its last proposal — catch it up so
                    # draft positions stay aligned with the target's
                    ingest[slot] = self._offsets[slot] - 1
                    need_ingest = True
            # the round's m tokens share its wall-clock gap evenly, so
            # inter-token percentiles measure the effective per-token rate
            per_tok_ms = gap_ms / m if (gap_ms is not None and m) else None
            post.append(("tokens", slot, req, toks, per_tok_ms, now))
        self._close_batch_span(rec, now)
        if need_ingest:
            (ing_offs,) = self._audit.put((ingest,))
            dad = ((self._adapter_pool, self._atables_dev)
                   if self._draft_lora else (None, None))
            _, self._draft_caches, self._draft_valid = \
                self._draft_model.decode_slots(
                    last_prop[:, None], ing_offs, self._draft_caches,
                    self._draft_valid, apool=dad[0], atables=dad[1])
        return post

    def _finish_decode(self, post: list, outputs: list) -> None:
        """The collected step's deferred host work — stream callbacks,
        inter-token telemetry, terminal emission (stats serialization) —
        run while the next decode executes on the device."""
        for kind, slot, req, tok, ms, now in post:
            if kind == "tokens":
                # one speculative round's committed run (tok is a list)
                for t in tok:
                    if ms is not None:
                        self._observe_intertoken(req, ms)
                    if req.stream_cb is not None:
                        req.stream_cb(req, t)
                if req.done:
                    outputs.append(self._emit(req, now))
                continue
            if kind == "fail":
                logger.warning(
                    "serving: request %d failed (%s) after %d tokens — "
                    "slot %d quarantined and freed", req.request_id,
                    FAIL_NON_FINITE, len(req.generated), slot)
                outputs.append(self._emit(req, now))
                continue
            if ms is not None:
                self._observe_intertoken(req, ms)
            if req.stream_cb is not None:
                req.stream_cb(req, tok)
            if req.done:
                outputs.append(self._emit(req, now))

    def _observe_intertoken(self, req: Request, ms: float) -> None:
        """Record one inter-token gap on the request, the global histogram,
        and the request's priority-class histogram (the per-tier p99 is the
        SLO headline)."""
        req.intertoken_ms.append(ms)
        self.registry.histogram(
            "serving/intertoken_ms", MS_BUCKETS).observe(ms)
        self.registry.histogram(
            f"serving/intertoken_ms_{req.priority}", MS_BUCKETS).observe(ms)

    def _stop_reason(self, req: Request, tok: int) -> Optional[str]:
        """Finish reason for ``tok`` (already appended), engine-level EOS
        included — the ONE stop predicate the prefill's first token, the
        decode collect and the speculative collect share."""
        reason = req.check_stop(tok)
        if (reason is None and self.eos_token_id is not None
                and tok == self.eos_token_id):
            reason = "stop_token"  # engine-level EOS (tokenizer-wide)
        return reason

    def _finish_request(self, slot: int, req: Request, reason: str,
                        now: float) -> None:
        """Terminal FINISHED bookkeeping: state, slot release, park, and
        (paged) page reclamation."""
        req.transition(RequestState.FINISHED)
        req.finish_reason = reason
        req.finish_time = now
        self._trace_end_phase(req, t=now)
        self.scheduler.release(req)
        self._park_slot(slot)
        self.registry.counter("serving/finished_total").inc()

    def _fail_slot_state(self, slot: int, req: Request, now: float,
                         reason: str = FAIL_NON_FINITE) -> None:
        """Quarantine bookkeeping for one failed request: terminal
        ``FAILED`` state, slot freed and parked (the next insert overwrites
        the poisoned KV; a parked row's logits are ignored meanwhile), its
        KV pages reclaimed, the rest of the batch untouched."""
        req.transition(RequestState.FAILED)
        req.finish_reason = reason
        req.finish_time = now
        self._trace_end_phase(req, t=now, failed=reason)
        self.scheduler.release(req)
        self._chunking.pop(slot, None)
        self._park_slot(slot)
        self.registry.counter("serving/failed_total").inc()

    def _fail_slot(self, slot: int, req: Request, outputs: list,
                   now: float) -> None:
        """Quarantine at the prefill's first token: bookkeeping + log +
        emit in one go (the decode loop defers log and emit to
        :meth:`_finish_decode`)."""
        self._fail_slot_state(slot, req, now)
        logger.warning(
            "serving: request %d failed (%s) after %d tokens — slot %d "
            "quarantined and freed", req.request_id, FAIL_NON_FINITE,
            len(req.generated), slot)
        outputs.append(self._emit(req, now))

    def _append_token(self, slot: int, req: Request, tok: int, now: float) -> None:
        """Record + stream one generated token; finish the request when it
        hits a stop condition (slot freed immediately)."""
        req.generated.append(tok)
        req.weights_version = self.weights_version
        self._last_tok_time[slot] = now
        self.registry.counter("serving/tokens_total").inc()
        if req.stream_cb is not None:
            req.stream_cb(req, tok)
        reason = self._stop_reason(req, tok)
        if reason is not None:
            self._finish_request(slot, req, reason, now)

    def _release_adapter(self, slot: int) -> None:
        """Release the slot's adapter pin (release-on-terminal, the other
        half of pin-at-admission) and null its table row.  Idempotent —
        terminal paths and the sweep's park both call it."""
        if self._adapters is None:
            return
        aid = self._slot_adapter[slot]
        if not aid:
            return
        self._adapters.release(aid)
        self._slot_adapter[slot] = 0
        self._adapter_tables[slot] = 0
        self._adapter_dirty = True

    def _park_slot(self, slot: int) -> None:
        """Make a slot that lost its occupant inert until its next insert —
        every release path ends here, and each piece is idempotent: offset
        ``T`` writes nothing, the pages and the adapter pin go back, and
        the sampler row returns to greedy, so a finished sampled request
        does not hold the batch's sampler on its sort (``_sample_rows``
        picks its work from every row it is handed)."""
        self._offsets[slot] = self.T
        self._last_tok_time[slot] = None
        self._kv.release_slot(slot)
        self._release_adapter(slot)
        if self._temps[slot] > 0.0:
            self._temps[slot] = 0.0
            self._sampling_dirty = True

    def _park_free_slots(self) -> None:
        """Park every slot without a live occupant (after a sweep freed
        cancelled/timed-out requests)."""
        live = {slot for slot, _ in self.scheduler.active()}
        for slot in range(self.B):
            if slot not in live:
                self._chunking.pop(slot, None)  # abandon a mid-chunk prefill
                self._park_slot(slot)

    def _emit(self, req: Request, now: float) -> RequestOutput:
        if req.parked_at is not None:
            # terminal while parked (sweep/cancel between a preemption and
            # its re-grant): the open park still counts as preempted time
            req.preempted_ms += max(now - req.parked_at, 0.0) * 1e3
            req.parked_at = None
        # terminal while holding a resume pin (swept/cancelled parked
        # victim): the pin drops here, the one choke point every terminal
        # path funnels through — zero page leak
        self._kv.release_resume(req)
        tr = self.tracer
        if tr is not None:
            rt = self._rt.pop(req.request_id, None)
            if rt is not None:
                tr.end(rt.pop("phase", None), t=now)  # defensive: none open
                tr.end(rt.get("root"), t=now, state=req.state.value,
                       finish_reason=req.finish_reason,
                       new_tokens=len(req.generated),
                       preemptions=req.preemptions)
        out = RequestOutput.from_request(req, now)
        if self._stats_path is not None:
            if self._stats_f is None:
                self._stats_f = open(self._stats_path, "a")
            rec = {
                "schema": SERVING_STATS_SCHEMA,
                "time": time.time(),
                "request_id": out.request_id,
                "state": out.state,
                "finish_reason": out.finish_reason,
                "prompt_len": out.prompt_len,
                "new_tokens": len(out.token_ids),
                "queue_ms": out.queue_ms,
                "ttft_ms": out.ttft_ms,
                "total_ms": out.total_ms,
                # speculative decoding accounting (zeros / null off spec)
                "spec_proposed": out.spec_proposed,
                "spec_accepted": out.spec_accepted,
                "acceptance_rate": out.acceptance_rate,
                # tenancy: which LoRA adapter served it (0 = base model)
                "adapter_id": out.adapter_id,
                # SLO scheduling (v4): priority class, deadline budget,
                # queue wait, preemption round-trips, and — for requests
                # the engine shed pre-prefill — why
                "priority": out.priority,
                "deadline_s": out.deadline_s,
                "queue_wait_ms": out.queue_ms,
                "preemptions": out.preemptions,
                "shed_reason": req.shed_reason,
                # tracing linkage + work decomposition (v5): the monotonic
                # stamp pairs the wall `time` (cross-replica sort under
                # clock skew) and comes from the ENGINE clock so it shares
                # the spans' timescale; trace_id keys this request's spans
                # in trace_events.jsonl (null when no tracer is attached)
                "mono": self._clock(),
                "decode_steps": out.decode_steps,
                "prefill_chunks": out.prefill_chunks,
                "preempted_ms": out.preempted_ms,
                "trace_id": out.trace_id,
                # live weights (v6): the version that decoded the last
                # committed token (0 = process-start, never swapped)
                "weights_version": out.weights_version,
            }
            self._stats_f.write(json.dumps(rec) + "\n")
            self._stats_f.flush()
        if self._health is not None:
            # per-class deadline attainment feeds the SLO burn-rate
            # windows: good = finished within its deadline
            self._health.note_output(out, now)
        return out
