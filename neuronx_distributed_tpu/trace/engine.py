"""Inference trace engine: AOT-compiled context-encoding + token-generation.

TPU-native replacement for the reference's inference stack
(``src/neuronx_distributed/trace/trace.py:24-214`` and the split
context/decode models of
``examples/inference/llama2/neuron_modeling_llama.py:292-342,437-465``).
Where the reference spawns one process per TP rank, traces each shard through
``torch_neuronx`` into a NEFF and juggles concurrent collective loading
(``trace.py:32-53``), here one SPMD program per phase is lowered ahead of time
with ``jax.jit(...).lower(...).compile()`` over the global mesh — the XLA TPU
compiler plays neuronx-cc, and GSPMD plays the per-shard process fleet.

Two executables, mirroring the reference's split:

- **context**: prefill the padded prompt, build the KV caches, return the
  last-position logits;
- **decode**: one token step against the caches; the caches are DONATED so
  XLA aliases the update in place — the functional analogue of the
  reference's KV-cache-as-aliased-parameters trick
  (``neuron_modeling_llama.py:437-450``).

The decode offset is a traced scalar, so one compiled program serves every
step (static shapes, dynamic position).  Ragged batches are served with
LEFT-padded prompts: a per-example key-validity mask rides through both
phases (the reference's padded HF batches,
``neuron_modeling_llama.py:437-465``), RoPE positions are recovered from the
mask (position = number of valid keys before the token), and padded rows
influence nothing — verified against per-example unpadded references.

``generate`` drives a THIRD executable by default: ``decode_loop``, the whole
``max_new_tokens`` sample-append-attend loop as one ``lax.scan`` inside one
jit — no per-token host round-trip (round-2 verdict weak #7).  The
single-step ``decode`` remains for per-token latency percentiles and the
export path.
"""

from __future__ import annotations

import collections
import dataclasses
import numbers
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_tpu.obs import startup
from neuronx_distributed_tpu.parallel.mesh import (
    BATCH_AXES,
    TENSOR_AXIS,
    get_data_parallel_size,
    get_mesh,
    model_parallel_is_initialized,
    named_sharding,
)
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

# default bound on each lazily-jitted per-shape executable cache (decode
# loops per n, chunk scorers per length, serving phase fns) — a long-lived
# serving process must not grow compile caches without limit
COMPILED_CACHE_SIZE = 8

# the serving phase-fn family is wider than the per-shape caches: paged +
# contiguous phase fns plus one verify program per speculative chunk width
# must coexist without evicting each other (an eviction on the serving hot
# path is a silent recompile every engine step — the spec tests assert
# trace/compiled_cache_evictions_total stays 0)
SERVING_CACHE_SIZE = 2 * COMPILED_CACHE_SIZE

# salted per-request sub-streams for speculative decoding: accept coins and
# residual resampling must not collide with the token-index sampling stream
# (shared by the solo speculative_generate and the serving engine's batched
# draft-k-verify)
SPEC_ACCEPT_SALT = 7919
SPEC_RESIDUAL_SALT = 104729


class _CompiledLRU:
    """Small LRU for lazily-jitted executables, keyed by shape-ish tuples.

    ``owner`` is the serving wrapper; when it carries a ``metrics_registry``
    (an ``obs.MetricRegistry``, set by the serving engine), evictions are
    counted there as ``trace/compiled_cache_evictions_total`` so a long-lived
    server's recompile churn is visible in the persisted telemetry.

    When the owner additionally carries a ``compile_ledger`` (an
    ``obs.CompileLedger``, set by the serving engine or the wrapper's
    ``compile_ledger=`` kwarg), every cache event is accounted there too:
    hits/misses as counters, evictions as rows carrying the EVICTED
    ``(family, key)`` so thrash is attributable to the programs actually
    cycling, and each entry's FIRST call is timed as that program's cold
    compile (the timing wrapper then replaces itself with the raw fn, so
    steady-state calls pay nothing).  Ledger-off is one ``getattr`` per
    lookup — no allocation."""

    def __init__(self, name: str, capacity: int = COMPILED_CACHE_SIZE,
                 owner: Any = None):
        from collections import OrderedDict

        self.name = name
        self.capacity = max(1, int(capacity))
        self.owner = owner
        self._d: "OrderedDict" = OrderedDict()

    def get(self, key):
        fn = self._d.get(key)
        led = getattr(self.owner, "compile_ledger", None)
        if led is not None:
            (led.cache_hit if fn is not None else led.cache_miss)(self.name)
        if fn is not None:
            self._d.move_to_end(key)
        return fn

    def _family(self, key) -> str:
        """Ledger program family for a cache key: the shared serving cache
        keys lead with the phase-fn name (``("decode_pages", "fp", True)``,
        ``"prefill_one"``), which IS the program family — per-family
        attribution is what makes thrash diagnosable.  Keys without a
        leading name (the per-shape decode_loop / score_chunk caches) fall
        back to the cache name."""
        if isinstance(key, tuple) and key and isinstance(key[0], str):
            return key[0]
        if isinstance(key, str):
            return key
        return self.name

    def _timed_first_call(self, key, fn):
        """First-call compile timing: the first invocation of a lazily
        jitted entry traces + compiles synchronously before dispatch
        returns, so its wall time IS the cold-compile cost.  After the
        first call the raw fn replaces the wrapper in the cache — zero
        overhead on the steady path."""
        def first_call(*args, **kwargs):
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            wall_ms = (time.perf_counter() - t0) * 1e3
            if self._d.get(key) is first_call:  # unwrap unless evicted
                self._d[key] = fn
            led = getattr(self.owner, "compile_ledger", None)
            if led is not None:
                led.record_compile(self._family(key), key, wall_ms,
                                   kind="jit")
            return out

        return first_call

    def put(self, key, fn):
        """Store ``fn`` and return the STORED callable — the timing wrapper
        when a ledger is attached.  Call sites must invoke the return value
        (not their local ``fn``), or the first — compiling — invocation
        would bypass the wrapper and the cold compile would go unrecorded."""
        led = getattr(self.owner, "compile_ledger", None)
        if led is not None:
            # the thrash threshold is the enclosing cache's capacity: one
            # family whose distinct keys alone exceed it is guaranteed to
            # cycle the LRU even with nothing else cached
            led.set_capacity(self._family(key), self.capacity)
            fn = self._timed_first_call(key, fn)
        self._d[key] = fn
        self._d.move_to_end(key)
        if len(self._d) > self.capacity:
            old_key, _ = self._d.popitem(last=False)
            logger.info(
                "compiled-fn cache %r evicted key %r (capacity %d)",
                self.name, old_key, self.capacity,
            )
            reg = getattr(self.owner, "metrics_registry", None)
            if reg is not None:
                reg.counter("trace/compiled_cache_evictions_total").inc()
            if led is not None:
                led.record_eviction(self._family(old_key), old_key,
                                    capacity=self.capacity)
        return fn

    def __len__(self) -> int:
        return len(self._d)


def request_rng(rng: jax.Array, request_id: int) -> jax.Array:
    """Per-request sampling stream: fold the request id into the batch-level
    key, so a sampled request's output depends only on ``(rng, request_id,
    token index)`` — never on which requests it happens to be co-batched
    with.  Shared convention between ``generate(request_ids=...)`` and the
    continuous-batching :class:`~..serving.ServingEngine`.

    Ids wider than 32 bits — the serving fleet's router-assigned
    ``(namespace << 32) | seq`` globals — fold the high word first, so two
    requests whose ids differ only in namespace draw disjoint streams.  Ids
    below 2**32 keep their historical single-fold streams bit-identical
    (traced int32 ids from ``generate(request_ids=...)`` can never exceed
    them).  Any host-side integral id counts (numpy scalars included —
    ``jnp.uint32`` would otherwise silently truncate a wide ``np.int64``
    into a colliding stream); traced values stay single-fold."""
    if isinstance(request_id, numbers.Integral):
        request_id = int(request_id)
        if request_id > 0xFFFFFFFF:
            rng = jax.random.fold_in(rng, jnp.uint32(request_id >> 32))
            request_id = request_id & 0xFFFFFFFF
    return jax.random.fold_in(rng, jnp.uint32(request_id))


def _temperature_logits(logits, temperature):
    """fp32 logits over the temperature (floored at 1e-6): what
    :func:`_filtered_logits` returns, bit for bit, when ``top_k == 0`` and
    ``top_p == 1`` — the serving sampler's temperature-only path draws from
    this and skips the sort."""
    return logits.astype(jnp.float32) / jnp.maximum(
        jnp.asarray(temperature, jnp.float32), 1e-6
    )


def _filtered_logits(logits, temperature, top_k=0, top_p=1.0):
    """Temperature/top-k/nucleus-filtered fp32 logits — the distribution the
    sampler actually draws from (dropped tokens at -inf-equivalent).  Shared
    by :func:`_sample_logits` and the sampled speculative-decoding accept
    test, which needs the filtered p/q distributions themselves."""
    logits = _temperature_logits(logits, temperature)
    neg = jnp.finfo(jnp.float32).min
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    # rank of each logit (0 = largest), traced-k-compatible via double argsort
    order = jnp.argsort(-logits, axis=-1)
    ranks = jnp.argsort(order, axis=-1)
    logits = jnp.where((top_k > 0) & (ranks >= top_k), neg, logits)
    # nucleus: drop tokens whose PRECEDING sorted mass reaches top_p
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < top_p  # always keeps >= 1 token
    # the cutoff is the SMALLEST kept logit: everything >= it is in the
    # nucleus (a max here would keep only the argmax — greedy in disguise)
    cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where((top_p < 1.0) & (logits < cutoff), neg, logits)


def _sample_logits(logits, rng, temperature, top_k=0, top_p=1.0):
    """Greedy / temperature / top-k / nucleus sampling.

    ``top_k > 0`` keeps only the k most likely tokens; ``top_p < 1`` keeps
    the smallest prefix of the sorted distribution whose mass reaches p
    (applied after top-k).  All three knobs may be TRACED scalars — one
    compiled program serves every sampler setting (per-request settings must
    not each pay an XLA compile).  A traced ``temperature`` always pays the
    whole filter (two sorts over the vocabulary) and selects greedy or
    sampled afterwards; only a Python-float ``temperature == 0.0`` takes the
    short-circuit, which serves ``generate()``'s greedy callers (no rng
    needed).  The serving engine's ``_sample_rows`` chooses the work once a
    batch instead and calls this only for a batch that filters.
    Serving parity with HF ``generate``'s standard sampler knobs (the
    reference drives its compiled pair through HF generate,
    ``neuron_modeling_llama.py:437-465``).

    ``rng`` may also be a BATCH of keys ``[B, 2]`` (one per example — the
    per-request streams of ``generate(request_ids=...)`` and the serving
    engine): each row is then drawn with its own key."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if isinstance(temperature, (int, float)) and float(temperature) == 0.0:
        return greedy
    filtered = _filtered_logits(logits, temperature, top_k, top_p)
    if rng is not None and jnp.ndim(rng) == 2 and logits.ndim == 2:
        sampled = jax.vmap(
            lambda key, lg: jax.random.categorical(key, lg, axis=-1)
        )(rng, filtered).astype(jnp.int32)
    else:
        sampled = jax.random.categorical(rng, filtered, axis=-1).astype(jnp.int32)
    return jnp.where(jnp.asarray(temperature, jnp.float32) > 0.0, sampled, greedy)


def parallel_model_trace(
    fn: Callable,
    *example_args,
    donate_argnums: Sequence[int] = (),
    static_argnums: Sequence[int] = (),
    compile_ledger: Any = None,
):
    """AOT-compile ``fn`` for the given example arguments (shapes/dtypes are
    taken from them; values are ignored).

    Functional analogue of the reference's ``parallel_model_trace``
    (``trace/trace.py:118-186``): instead of per-rank subprocesses feeding
    neuronx-cc, the jit is lowered once over the live mesh and the XLA
    compiler emits the sharded program. Returns the compiled executable
    (callable with real arrays).  ``compile_ledger`` (an
    ``obs.CompileLedger``) records the compile's wall time + cost stats."""
    jitted = jax.jit(
        fn, donate_argnums=tuple(donate_argnums), static_argnums=tuple(static_argnums)
    )
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))
        if not isinstance(x, jax.ShapeDtypeStruct)
        else x,
        example_args,
    )
    lowered = jitted.lower(*shapes)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    if compile_ledger is not None:
        compile_ledger.record_compile(
            getattr(fn, "__name__", "fn"), "aot",
            (time.perf_counter() - t0) * 1e3, kind="aot", compiled=compiled)
    from neuronx_distributed_tpu.utils.profiling import cost_report

    logger.info(
        "traced %s: %s flops (per XLA cost analysis)",
        getattr(fn, "__name__", "fn"),
        cost_report(compiled).get("flops", "n/a"),
    )
    return compiled


@dataclasses.dataclass(frozen=True)
class InferenceConfig:
    """Serving shapes — fixed at trace time, like the reference's compiled
    context/decode NEFF pair.

    ``chunked_prefill`` compiles a THIRD executable that prefills
    ``context_len``-sized chunks at a traced cache offset, so prompts of any
    multiple of ``context_len`` (up to ``max_total_len``) are served by one
    compiled program instead of one trace per prompt length — the bounded-
    compile-shape answer to long prompts (the reference would need a new
    NEFF per context length)."""

    batch_size: int
    context_len: int
    max_total_len: int
    kv_cache_dtype: Any = jnp.bfloat16
    chunked_prefill: bool = False

    def __post_init__(self):
        if self.max_total_len < self.context_len:
            raise ValueError(
                f"max_total_len ({self.max_total_len}) < context_len ({self.context_len})"
            )


_BATCH_REPLICATION_WARNED: set = set()


def _serving_batch_axes(batch_size: int):
    """The one batch-dim sharding policy for serving arrays: over dp when
    divisible, else replicated (warn once per batch size — replication
    multiplies per-device memory).  Shared by cache construction and the
    executables' loop-array pinning so the two can never diverge."""
    if not model_parallel_is_initialized():
        return None
    dp = get_data_parallel_size()
    if batch_size % dp == 0:
        return BATCH_AXES
    if dp > 1 and (batch_size, dp) not in _BATCH_REPLICATION_WARNED:
        _BATCH_REPLICATION_WARNED.add((batch_size, dp))
        logger.warning(
            "serving batch dim (%d) not divisible by dp (%d); replicating",
            batch_size, dp,
        )
    return None


def _kv_cache_sharding(batch_size: int, num_kv_heads: int):
    """Sharding of a contiguous ``[B, T, NKV, D]`` KV cache: kv-heads over
    tp and batch over dp when a mesh is live (None without one)."""
    if not model_parallel_is_initialized():
        return None
    mesh = get_mesh()
    # shard only the dims the shapes actually divide (small serving
    # batches are often < dp; few kv heads may be < tp) — and say so,
    # since replication multiplies per-device cache memory
    batch_axes = _serving_batch_axes(batch_size)
    kv_axes = TENSOR_AXIS if num_kv_heads % mesh.shape[TENSOR_AXIS] == 0 else None
    if kv_axes is None and mesh.shape[TENSOR_AXIS] > 1:
        logger.warning(
            "kv cache head dim (%d) not divisible by tp (%d); replicating",
            num_kv_heads, mesh.shape[TENSOR_AXIS],
        )
    return named_sharding(batch_axes, None, kv_axes, None)


def init_kv_caches(
    num_layers: int,
    batch_size: int,
    max_total_len: int,
    num_kv_heads: int,
    head_dim: int,
    dtype: Any = jnp.bfloat16,
):
    """Zero KV caches ``[B, T, NKV, D]`` per layer, kv-heads sharded over tp
    and batch over dp when a mesh is live — born with that sharding, never
    staged whole on one device."""
    shape = (batch_size, max_total_len, num_kv_heads, head_dim)
    sharding = _kv_cache_sharding(batch_size, num_kv_heads)
    return [
        (jnp.zeros(shape, dtype, device=sharding),
         jnp.zeros(shape, dtype, device=sharding))
        for _ in range(num_layers)
    ]


class _ServingBase:
    """Shared generate/benchmark loop over ``(context, decode)`` executables;
    concrete classes provide ``self.context``, ``self.decode``,
    ``self.params`` and ``self.config``."""

    config: InferenceConfig
    params: Any
    context: Callable
    decode: Callable

    def _sample(self, logits, rng, temperature, top_k=0, top_p=1.0):
        if temperature > 0.0 and rng is None:
            raise ValueError("temperature sampling requires an rng key")
        return _sample_logits(logits, rng, temperature, top_k, top_p)

    def _valid_ctx(self, prompt_lens, length: Optional[int] = None) -> jax.Array:
        """Left-padded key-validity mask [B, length] from per-example lengths."""
        cfg = self.config
        B = cfg.batch_size
        C = cfg.context_len if length is None else length
        if prompt_lens is None:
            return jnp.ones((B, C), jnp.int32)
        lens = jnp.asarray(prompt_lens, jnp.int32)
        if lens.shape != (B,):
            raise ValueError(f"prompt_lens shape {lens.shape} != ({B},)")
        return (jnp.arange(C)[None, :] >= C - lens[:, None]).astype(jnp.int32)

    def _decode_step_traceable(self, params, tok, offset, caches, valid):
        """Single decode step in traceable (jit-composable) form; concrete
        classes bind it to the pure phase fn or the exported program."""
        raise NotImplementedError

    def _decode_loop(self, n: int):
        """Compiled n-step decode: sample → append → attend as one
        ``lax.scan`` under one jit (no per-token host sync).  Sampler knobs
        (temperature / top_k / top_p) are RUNTIME scalars, so one compiled
        loop per ``n`` serves every per-request sampler setting."""
        if not hasattr(self, "_loop_cache"):
            self._loop_cache = _CompiledLRU("decode_loop", owner=self)
        fn = self._loop_cache.get(n)
        if fn is not None:
            return fn

        def loop(params, first_tok, start, caches, valid, rngs,
                 temperature, top_k, top_p):
            def step(carry, rng_i):
                tok, offset, caches, valid = carry
                logits, caches, valid = self._decode_step_traceable(
                    params, tok, offset, caches, valid
                )
                nxt = _sample_logits(logits, rng_i, temperature, top_k, top_p)[:, None]
                return (nxt, offset + 1, caches, valid), nxt[:, 0]

            _, toks = jax.lax.scan(
                step, (first_tok, start, caches, valid), rngs, length=n
            )
            return toks.T  # [B, n]

        fn = jax.jit(loop, donate_argnums=(3,))
        fn = self._loop_cache.put(n, fn)
        return fn

    def generate(
        self,
        prompt_ids: jax.Array,
        max_new_tokens: int,
        temperature: float = 0.0,
        rng: Optional[jax.Array] = None,
        prompt_lens: Optional[jax.Array] = None,
        fused: bool = True,
        top_k: int = 0,
        top_p: float = 1.0,
        request_ids: Optional[Sequence[int]] = None,
    ) -> jax.Array:
        """Prefill + fixed-length decode; returns ``[B, C + max_new_tokens]``.

        ``prompt_lens`` (per-example lengths; prompts LEFT-padded to C)
        enables ragged batches.  ``fused`` (default) runs the whole decode as
        one jitted ``lax.scan`` — zero host round-trips; ``fused=False``
        steps the single-token executable (the reference's per-token
        HF-generate driving, ``neuron_modeling_llama.py:437-465``).

        ``request_ids`` (one int per example, with ``rng``) switches sampling
        to PER-REQUEST rng streams: row ``b`` draws token ``i`` with
        ``fold_in(fold_in(rng, request_ids[b]), i)`` (:func:`request_rng`),
        so a sampled request's output is reproducible regardless of which
        requests it is co-batched with — the continuous-batching
        :class:`~..serving.ServingEngine` samples from the same streams."""
        cfg = self.config
        B, C = prompt_ids.shape
        chunk = cfg.context_len
        # length bounds are the max_total_len check's job, not the shape
        # check's; C > 0 guards the degenerate empty prompt
        chunkable = cfg.chunked_prefill and C > 0 and C % chunk == 0
        if B != cfg.batch_size or (C != chunk and not chunkable):
            raise ValueError(
                f"prompt shape {(B, C)} does not match traced shape "
                f"{(cfg.batch_size, chunk)}"
                + (
                    "" if cfg.chunked_prefill
                    else " (chunked_prefill=True serves any multiple of context_len)"
                )
            )
        if C + max_new_tokens > cfg.max_total_len:
            raise ValueError(
                f"context {C} + new {max_new_tokens} exceeds max_total_len {cfg.max_total_len}"
            )
        T = cfg.max_total_len
        if C == chunk:
            valid = self._valid_ctx(prompt_lens)
            logits, caches = self.context(self.params, prompt_ids.astype(jnp.int32), valid)
            valid_full = jnp.concatenate(
                [valid, jnp.zeros((B, T - C), jnp.int32)], axis=1
            )
        else:
            # chunked prefill: one compiled chunk program, host loop over
            # offsets — prompts left-padded to C, validity precomputed over
            # the whole cache so chunk positions see the global prefix counts
            if not hasattr(self, "prefill_chunk"):
                raise ValueError(
                    "this serving wrapper has no compiled chunk-prefill "
                    "executable (exported models carry only context/decode); "
                    "re-trace with InferenceConfig(chunked_prefill=True)"
                )
            valid = self._valid_ctx(prompt_lens, C)
            valid_full = jnp.concatenate([valid, jnp.zeros((B, T - C), jnp.int32)], 1)
            caches = self.empty_caches()
            ids = prompt_ids.astype(jnp.int32)
            for i in range(C // chunk):
                logits, caches = self.prefill_chunk(
                    self.params, ids[:, i * chunk:(i + 1) * chunk],
                    jnp.int32(i * chunk), caches, valid_full,
                )
        row_keys = None
        if request_ids is not None:
            if rng is None:
                raise ValueError("request_ids requires an rng key")
            rids = jnp.asarray(request_ids, jnp.uint32)
            if rids.shape != (B,):
                raise ValueError(f"request_ids shape {rids.shape} != ({B},)")
            row_keys = jax.vmap(lambda r: request_rng(rng, r))(rids)  # [B, 2]

        def tok_rng(i):
            """Key(s) for generated-token index ``i``: shared fold_in stream,
            or per-request streams when ``request_ids`` is given."""
            if rng is None:
                return None
            if row_keys is None:
                return jax.random.fold_in(rng, i)
            return jax.vmap(lambda k: jax.random.fold_in(k, i))(row_keys)

        first = self._sample(logits, tok_rng(0), temperature, top_k, top_p)[:, None]
        if max_new_tokens == 1:
            return jnp.concatenate([prompt_ids, first], axis=1)

        n_more = max_new_tokens - 1
        if fused:
            # one vmapped fold_in (not n host dispatches); indices 1..n match
            # the stepped path's per-step fold_in exactly (parity-tested).
            # Per-request streams carry [n, B, 2] keys through the scan.
            if rng is None:
                rngs = jnp.zeros((n_more, 2), jnp.uint32)
            else:
                rngs = jax.vmap(tok_rng)(jnp.arange(1, n_more + 1))
            more = self._decode_loop(n_more)(
                self.params, first, jnp.int32(C), caches, valid_full, rngs,
                jnp.float32(temperature), jnp.int32(top_k), jnp.float32(top_p),
            )
            return jnp.concatenate([prompt_ids, first, more], axis=1)

        toks = [prompt_ids, first]
        nxt = first
        for step in range(n_more):
            step_rng = tok_rng(1 + step)
            logits, caches, valid_full = self.decode(
                self.params, nxt, jnp.int32(C + step), caches, valid_full
            )
            nxt = self._sample(logits, step_rng, temperature, top_k, top_p)[:, None]
            toks.append(nxt)
        return jnp.concatenate(toks, axis=1)

    def benchmark(
        self, max_new_tokens: int = 64, warmup: int = 1, prompt_ids=None,
        registry=None,
    ) -> dict:
        """Decode latency/throughput — the neuronperf-equivalent harness
        (reference ``examples/inference/benchmark.py:53-77``): per-token
        p50/p99 ms, context-encode ms, tokens/s.

        ``registry`` (an ``obs.MetricRegistry``) additionally feeds the
        serving histograms: ``serving/ttft_ms`` (context encode — the
        time-to-first-token component) and ``serving/decode_ms`` (per-token
        step latency), so serving runs leave the same persisted telemetry
        as training runs."""
        cfg = self.config
        B, C, T = cfg.batch_size, cfg.context_len, cfg.max_total_len
        if prompt_ids is None:
            prompt_ids = jnp.zeros((B, C), jnp.int32)
        for _ in range(warmup):
            # warm BOTH decode paths before timing: the fused n-step loop
            # (throughput section) and the single-step executable (latency
            # section — on LoadedInferenceModel it is a lazy jit that would
            # otherwise compile inside the timed loop and poison p99)
            jax.block_until_ready(self.generate(prompt_ids, max_new_tokens))
            jax.block_until_ready(
                self.generate(prompt_ids, min(2, max_new_tokens), fused=False)
            )

        valid_ctx = jnp.ones((B, C), jnp.int32)
        t0 = time.perf_counter()
        logits, caches = jax.block_until_ready(
            self.context(self.params, prompt_ids, valid_ctx)
        )
        context_ms = (time.perf_counter() - t0) * 1e3

        # per-token latency percentiles: the single-step executable
        valid = jnp.concatenate([valid_ctx, jnp.zeros((B, T - C), jnp.int32)], 1)
        lat = []
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        for step in range(max_new_tokens):
            t0 = time.perf_counter()
            logits, caches, valid = self.decode(
                self.params, nxt, jnp.int32(C + step), caches, valid
            )
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            jax.block_until_ready(nxt)
            lat.append((time.perf_counter() - t0) * 1e3)
        lat_arr = np.asarray(lat)
        total_s = lat_arr.sum() / 1e3
        if registry is not None:
            from neuronx_distributed_tpu.obs import MS_BUCKETS

            registry.histogram("serving/ttft_ms", MS_BUCKETS).observe(context_ms)
            decode_hist = registry.histogram("serving/decode_ms", MS_BUCKETS)
            for ms in lat:
                decode_hist.observe(ms)

        # steady-state throughput: the fused scan loop (no host round-trips);
        # generate() includes the prefill, so subtract the measured context time
        t0 = time.perf_counter()
        jax.block_until_ready(self.generate(prompt_ids, max_new_tokens, fused=True))
        fused_s = max(time.perf_counter() - t0 - context_ms / 1e3, 1e-9)

        return {
            "context_ms": context_ms,
            "token_p50_ms": float(np.percentile(lat_arr, 50)),
            "token_p99_ms": float(np.percentile(lat_arr, 99)),
            "tokens_per_s": float(B * max_new_tokens / total_s),
            "tokens_per_s_fused": float(B * max_new_tokens / fused_s),
            "new_tokens": max_new_tokens,
            "batch_size": B,
        }


class ParallelInferenceModel(_ServingBase):
    """Compiled serving wrapper — the ``TensorParallelNeuronModel`` analogue
    (``trace/trace.py:24-68``), holding the context + decode executables and
    a greedy/temperature ``generate`` loop.

    ``module`` must follow the framework KV-cache protocol (as
    ``LlamaForCausalLM`` does): ``apply(params, ids, positions, kv_caches,
    cache_offset) -> (logits, new_caches)``.
    """

    @startup.phased("engine")
    def __init__(
        self,
        module,
        params,
        config: InferenceConfig,
        num_layers: Optional[int] = None,
        num_kv_heads: Optional[int] = None,
        head_dim: Optional[int] = None,
        paged_kernel: Any = "auto",
        compile_ledger: Any = None,
    ):
        mcfg = getattr(module, "config", None)
        self.module = module
        self.params = params
        self.config = config
        # compile accounting (obs.CompileLedger): the AOT builds below and
        # every _CompiledLRU family report their compiles/evictions here.
        # None = off (allocation-free — each site is one getattr); the
        # serving engine attaches its own ledger to this attribute when
        # given one explicitly.
        self.compile_ledger = compile_ledger
        self.num_layers = num_layers if num_layers is not None else mcfg.num_layers
        self.num_kv_heads = num_kv_heads if num_kv_heads is not None else mcfg.num_kv_heads
        self.head_dim = head_dim if head_dim is not None else mcfg.head_dim_
        # block-table-native paged decode (ops.paged_attention): "auto"
        # resolves to the kernel when the programs run on a TPU — decided by
        # the devices they are placed on (the mesh's, else the params'),
        # not by the process's default backend — and to the [B, T] gather
        # path elsewhere; the per-call `paged_kernel=` kwarg on
        # decode_pages / decode_pages_lora / verify_pages overrides this
        # default (each value compiles its own cached program)
        from neuronx_distributed_tpu.ops.paged_attention import (
            resolve_paged_kernel,
        )

        self.paged_kernel = resolve_paged_kernel(
            paged_kernel, self._placement_platform())
        # a routed model's paged programs also return their routing
        # (models.llama.moe_layer_stats); it waits here, still on the
        # device, for whoever reads the step's tokens to take it along
        self._moe = getattr(mcfg, "num_experts", 1) > 1
        # its routed layers: all of them, or those a layer list names
        self._moe_layers = getattr(mcfg, "moe_layers", None) \
            or self.num_layers
        self._moe_stats: collections.deque = collections.deque(maxlen=256)
        self.moe_seq = 0      # paged programs of a routed model run so far
        # a model with a layer LIST (LlamaConfig.mixer_types): which layers
        # keep a recurrent state row a sequence and which choose the pages
        # they attend (the paged programs then also return that choice)
        self.recurrent = bool(getattr(mcfg, "recurrent_layers", ()))
        from neuronx_distributed_tpu.models.hybrid import kinds_of

        mixers = kinds_of(mcfg)
        for kind in mixers:
            if kind.unserved is not None:
                raise ValueError(kind.unserved)
        # layers that step their state arrays where they lie when every
        # slot is a batch row (``models.hybrid.Mamba2Mixer``): a decode is
        # then told no rows
        self._rows_in_place = any(kind.rows_in_place for kind in mixers)
        self._sparse = tuple(getattr(mcfg, "selecting_layers", ()))
        self._sparse_stats: collections.deque = collections.deque(maxlen=256)
        self._build()

    def take_sparse_stats(self) -> list:
        """One ``{"chosen": [Ls, B, NKV, PP] bool, "program": family}`` of
        device arrays for each paged program run since the last call,
        oldest first: the pages (in units of the slot's block table) that
        the LAST token row of each batch row attended in each block-sparse
        layer.  Empty for a model without such layers."""
        out = list(self._sparse_stats)
        self._sparse_stats.clear()
        return out

    def take_moe_stats(self, upto: Optional[int] = None) -> list:
        """One ``{"load": [L, E], "choice": [L, rows, K]}`` of device
        arrays, with the ``"program"`` family that ran (``decode_pages``,
        ``prefill_chunk_pages``, ...) and its ``"seq"`` (the value of
        ``moe_seq`` when it was launched), for each paged program run since
        the last call, oldest first; empty for a dense model.  ``upto``
        leaves the programs launched after that ``seq`` for a later call:
        a reader that waits for one program's result takes what the device
        has finished by then and nothing it would have to wait longer for."""
        out = []
        while self._moe_stats and (upto is None
                                   or self._moe_stats[0]["seq"] <= upto):
            out.append(self._moe_stats.popleft())
        return out

    def _placement_platform(self) -> str:
        """Platform of the devices this wrapper's programs are placed on."""
        if model_parallel_is_initialized():
            return get_mesh().devices.flat[0].platform
        sharding = getattr(jax.tree.leaves(self.params)[0], "sharding", None)
        if sharding is None:  # host arrays: jit places them by default
            return jax.devices()[0].platform
        return next(iter(sharding.device_set)).platform

    # -- phase functions (pure; also used by the export path) --------------

    def _apply_row(self, params, ids, *args, row=None, mutable=False, **kw):
        """``module.apply`` for a caller that reads ONE row's logits: the
        backbone runs whole and the head gets that row of its final-norm
        hidden states (``[B, S, H]`` -> ``[B, 1, H]``), chosen BEFORE the
        matmul — the compiler cannot sink a traced slice through it, and a
        512-row chunk would pay the whole ``[S, vocab]`` product for a row.
        ``row`` is a traced scalar, or ``None`` for the last row; a ``row``
        below 0 says that NO row is read: the head is not run and the logits
        are zeros.  At ``S = 1`` the choice is the identity and the module
        is applied whole (the decode programs are what they were).  Returns
        ``(logits [B, V], caches, collections or None)``."""
        def apply(**how):
            out = self.module.apply(params, ids, *args, mutable=mutable,
                                    **how, **kw)
            return out if mutable else (out, None)

        if ids.shape[1] == 1:
            (logits, caches), stats = apply()
            return logits[:, -1, :], caches, stats
        # the scope flax gives ``__call__``: a layer's name stack (what a
        # trace is read by) is the same whichever way its program is built
        with jax.named_scope(type(self.module).__name__):
            (h, caches), stats = apply(method="backbone")

        def head():
            h_row = (h[:, -1:, :] if row is None else
                     jax.lax.dynamic_slice_in_dim(h, row, 1, axis=1))
            return self.module.apply(params, h_row, method="head")[:, 0, :]

        if row is None:
            return head(), caches, stats
        blank = jax.eval_shape(head)
        logits = jax.lax.cond(
            row >= 0, head, lambda: jnp.zeros(blank.shape, blank.dtype))
        return logits, caches, stats

    def _context_fn(self, params, ids, valid, adapters=None):
        """Prefill; ``valid [B, C]`` marks real (non-left-pad) prompt tokens.
        Positions come from the mask (a token's position = count of valid
        tokens before it), so ragged prompts get correct RoPE phases.
        ``adapters`` (the tenancy path) rides as an extra apply kwarg —
        passed only when set, so modules without the kwarg keep working."""
        B, C = ids.shape
        T = self.config.max_total_len
        positions = jnp.clip(jnp.cumsum(valid, axis=1) - 1, 0)
        kv_valid = jnp.concatenate(
            [valid, jnp.ones((B, T - C), jnp.int32)], axis=1
        )  # future cache slots are gated by the causal mask, not by validity
        caches = init_kv_caches(
            self.num_layers, B, T, self.num_kv_heads,
            self.head_dim, self.config.kv_cache_dtype,
        )
        extra = {} if adapters is None else {"adapters": adapters}
        logits, caches, _ = self._apply_row(
            params, ids, positions, caches, 0, kv_valid=kv_valid, **extra
        )
        return logits, caches

    def _decode_step_traceable(self, params, tok, offset, caches, valid):
        return self._decode_fn(params, tok, offset, caches, valid)

    def empty_caches(self):
        """Fresh zero contiguous ``[B, T]`` KV caches shaped/sharded like
        the traced ones: the solo ``generate``'s, and in the serving engine
        the speculative DRAFT's only (the target lives in a page pool)."""
        return init_kv_caches(
            self.num_layers, self.config.batch_size, self.config.max_total_len,
            self.num_kv_heads, self.head_dim, self.config.kv_cache_dtype,
        )

    def _prefill_chunk_fn(self, params, ids, offset, caches, valid):
        """Prefill one ``[B, Cc]`` chunk at (traced) cache ``offset``.

        ``valid [B, T]`` is the whole-cache key-validity mask with the full
        prompt's (left-padded) validity pre-written and zeros beyond it;
        chunk token positions are global prefix counts of that mask, so
        RoPE phases match the one-shot context exactly.  Keys beyond the
        chunk are causally masked (q_offset = cache offset), so the not-yet-
        written cache tail contributes nothing."""
        Cc = ids.shape[1]
        counts = jnp.cumsum(valid, axis=1) - valid  # valid keys strictly before
        positions = jax.lax.dynamic_slice_in_dim(counts, offset, Cc, axis=1)
        logits, caches, _ = self._apply_row(
            params, ids, positions.astype(jnp.int32), caches, offset, kv_valid=valid
        )
        return logits, caches

    def _score_chunk_fn(self, params, ids, offset, caches, valid):
        """Like :meth:`_prefill_chunk_fn` but (a) marks the chunk's cache
        slots valid itself (decode-phase convention: the tail starts as
        zeros) and (b) returns EVERY position's logits — the target-model
        verification step of speculative decoding, where position ``i``'s
        logits judge the draft's proposal ``i+1``."""
        B, Cc = ids.shape
        valid = jax.lax.dynamic_update_slice(
            valid, jnp.ones((B, Cc), valid.dtype), (0, offset)
        )
        counts = jnp.cumsum(valid, axis=1) - valid
        positions = jax.lax.dynamic_slice_in_dim(counts, offset, Cc, axis=1)
        logits, caches = self.module.apply(
            params, ids, positions.astype(jnp.int32), caches, offset, kv_valid=valid
        )
        return logits, caches, valid

    def score_chunk(self, ids, offset, caches, valid):
        """Compiled chunk scorer (lazily jitted per chunk length); outputs
        pinned to the same batch/cache shardings as the AOT executables so
        its caches/masks feed straight back into them."""
        if not hasattr(self, "_score_cache"):
            self._score_cache = _CompiledLRU("score_chunk", owner=self)
        fn = self._score_cache.get(ids.shape[1])
        if fn is None:
            io = self._io_shardings  # set by _build; unpinned outputs would
            # silently reintroduce the dp>1 placement mismatch, so fail loudly
            fn = jax.jit(self._score_chunk_fn, donate_argnums=(3,),
                         out_shardings=(None, io["cache_out"], io["batch"](None)))
            fn = self._score_cache.put(ids.shape[1], fn)
        return fn(self.params, ids, jnp.int32(offset), caches, valid)

    def _decode_fn(self, params, tok, offset, caches, valid):
        """One token step; ``valid [B, T]`` tracks key validity over the full
        cache.  Returns the updated mask so callers can thread it."""
        B = tok.shape[0]
        T = valid.shape[1]
        valid = valid.at[:, offset].set(1)  # the new token becomes a key
        # per-example position: number of valid keys strictly before offset
        before = jnp.where(jnp.arange(T)[None, :] < offset, valid, 0)
        positions = jnp.sum(before, axis=1, keepdims=True).astype(jnp.int32)
        logits, caches = self.module.apply(
            params, tok, positions, caches, offset, kv_valid=valid
        )
        return logits[:, -1, :], caches, valid

    # -- continuous-batching phase fns (serving/engine.ServingEngine) ------

    def _decode_slots_fn(self, params, tok, offsets, caches, valid,
                         apool=None, atables=None):
        """One token step with PER-SLOT cache offsets ``[B]`` — the
        continuous-batching generalization of :meth:`_decode_fn`: every slot
        writes its new key at its own position and takes its RoPE phase from
        its own validity prefix, so requests at different depths decode in
        one batched step.  An offset of ``T`` parks an idle slot (writes
        nothing).  ``apool``/``atables`` run the step under each slot's own
        LoRA adapter (the contiguous-cache counterpart of
        ``decode_pages_lora`` — an adapter-compatible spec DRAFT proposes
        under the request's adapter, keeping sampled self-draft output
        bit-identical to the plain engine's).  Returns
        ``(logits [B, V], caches, valid)``."""
        T = valid.shape[1]
        hot = jnp.arange(T)[None, :] == offsets[:, None]  # [B, T]
        valid = jnp.where(hot, 1, valid)  # the new token becomes a key
        # per-example position: number of valid keys strictly before offset
        before = jnp.where(jnp.arange(T)[None, :] < offsets[:, None], valid, 0)
        positions = jnp.sum(before, axis=1, keepdims=True).astype(jnp.int32)
        extra = ({} if apool is None
                 else {"adapters": self._gather_adapters(apool, atables)})
        logits, caches = self.module.apply(
            params, tok, positions, caches, offsets, kv_valid=valid, **extra
        )
        return logits[:, -1, :], caches, valid

    def _serving_lru(self, reset=False):
        """Get-or-create the shared serving phase-fn cache — the ONE place
        that owns its capacity (the paged programs, the draft's contiguous
        ones and the per-chunk-width verify programs must coexist without
        evictions)."""
        if reset or not hasattr(self, "_serving_cache"):
            self._serving_cache = _CompiledLRU(
                "serving_phase", capacity=SERVING_CACHE_SIZE, owner=self)
        return self._serving_cache

    def decode_slots(self, tok, offsets, caches, valid, apool=None,
                     atables=None):
        """Compiled per-slot decode step over contiguous ``[B, T]`` caches
        (lazily jitted, cache donated) — in the serving engine the
        speculative DRAFT's step and nothing else's: the target decodes
        through :meth:`decode_pages`.
        ``offsets`` is the per-slot next-write index ``[B]`` (``T`` = idle).
        ``apool``/``atables`` select the adapter-aware variant (its own
        cached program).  Outputs pinned to the AOT executables'
        shardings."""
        self._serving_lru()
        lora = apool is not None
        name = "decode_slots_lora" if lora else "decode_slots"
        fn = self._serving_cache.get(name)
        if fn is None:
            io = self._io_shardings
            fn = jax.jit(self._decode_slots_fn, donate_argnums=(3,),
                         out_shardings=(None, io["cache_out"], io["batch"](None)))
            fn = self._serving_cache.put(name, fn)
        args = (self.params, tok, jnp.asarray(offsets, jnp.int32), caches,
                valid)
        if lora:
            args = args + (apool, jnp.asarray(atables, jnp.int32))
        return fn(*args)

    def prefill_one(self, ids, valid):
        """Single-request prefill ``[1, C] -> (logits [1, V], caches B=1)``
        — the same pure phase fn as the batched ``context`` executable, so a
        slot-inserted request's prefill is numerically identical to a solo
        ``generate``'s.  The returned one-row caches feed
        :meth:`insert_slot`.  In the serving engine the speculative DRAFT's
        prefill only: the target's prompts go through
        :meth:`prefill_chunk_pages`."""
        self._serving_lru()
        fn = self._serving_cache.get("prefill_one")
        if fn is None:
            fn = jax.jit(self._context_fn)
            fn = self._serving_cache.put("prefill_one", fn)
        return fn(self.params, ids.astype(jnp.int32), valid)

    def _insert_slot_fn(self, caches, row_caches, valid, row_valid, slot):
        """Scatter a prefilled request into live batch state: write the
        one-row KV caches and validity row at batch index ``slot`` (traced,
        so one compiled program serves every slot)."""
        caches = jax.tree.map(
            lambda c, r: jax.lax.dynamic_update_slice_in_dim(
                c, r.astype(c.dtype), slot, axis=0),
            caches, row_caches,
        )
        valid = jax.lax.dynamic_update_slice_in_dim(valid, row_valid, slot, axis=0)
        return caches, valid

    def insert_slot(self, caches, row_caches, valid, row_valid, slot):
        """Compiled slot insert into contiguous ``[B, T]`` caches (live
        caches + validity donated — requests enter the batch without
        copying the other slots).  In the serving engine the speculative
        DRAFT's only; the target inserts a validity row
        (:meth:`insert_valid`) and writes K/V through its block table."""
        self._serving_lru()
        fn = self._serving_cache.get("insert_slot")
        if fn is None:
            io = self._io_shardings
            fn = jax.jit(self._insert_slot_fn, donate_argnums=(0, 2),
                         out_shardings=(io["cache_out"], io["batch"](None)))
            fn = self._serving_cache.put("insert_slot", fn)
        return fn(caches, row_caches, self._batch_committed(valid),
                  jnp.asarray(row_valid, jnp.int32), jnp.int32(slot))

    def _batch_committed(self, valid):
        """``valid`` (or a step's ``[B, S]`` tokens: :meth:`_paged_phase`)
        as int32, committed to the batch sharding.  An engine's
        first validity array is a fresh, uncommitted ``zeros``; every later
        one comes out of a program pinned to that sharding.  Committing the
        argument gives the inserts ONE signature: without it the first
        admission after a decode compiled them a second time, inside jit
        dispatch, where only the compile ledger's listener sees it."""
        return jax.device_put(valid.astype(jnp.int32),
                              self._io_shardings["batch"](None))

    # -- paged-KV phase fns (kvcache/ subsystem; serving paged mode) --------

    def make_page_pool(self, num_pages, page_size: int,
                       quant: Optional[str] = None):
        """A :class:`~..kvcache.pool.PagePool` shaped/sharded for this
        model's layers and cache dtype — the device half of the paged
        serving engine's KV state.  ``quant="int8"`` builds the quantized
        layout (int8 pages + per-page fp32 scale/zero; see
        :mod:`~..kvcache.quant`) — roughly 2x the pages per HBM byte.
        ``num_pages`` is one count, or — for a model whose layers keep pages
        of several kinds (``kvcache.pool.page_kinds``: window layers beside
        global ones) — one a kind: each layer's arrays have its kind's."""
        from neuronx_distributed_tpu.kvcache.pool import (
            LayerStates,
            PagePool,
            page_kinds,
        )

        mcfg = getattr(self.module, "config", None)
        # a state row a slot: a recurrent layer's batch row b continues row b
        layers = LayerStates.for_config(
            mcfg, page_size, state_rows=self.config.batch_size)
        return PagePool(self.num_layers, num_pages, page_size,
                        self.num_kv_heads, self.head_dim,
                        self.config.kv_cache_dtype, quant=quant,
                        kinds=page_kinds(mcfg),
                        **({} if layers is None else {"layers": layers}))

    @staticmethod
    def _pool_tag(caches) -> str:
        """Compiled-cache key component distinguishing pool layouts: the
        quantized six-tuple-per-layer pool and the fp pair compile to
        different programs with different pinned out-shardings."""
        return "int8" if len(caches[0]) == 6 else "fp"

    def _pool_out_shardings(self, caches):
        from jax.sharding import NamedSharding

        return jax.tree.map(
            lambda x: x.sharding
            if isinstance(getattr(x, "sharding", None), NamedSharding)
            else None,
            caches)

    def _paged_step_fn(self, params, toks, offsets, block_table, caches,
                       valid, apool=None, atables=None, last_row=None,
                       state_rows=None, paged_kernel=False, update_valid=True,
                       last_only=True):
        """THE paged phase fn — one parameterized family serving decode,
        multi-adapter decode, speculative verify and chunked prefill (the
        former ``_decode_pages_fn`` / ``_decode_pages_lora_fn`` /
        ``_verify_pages_fn`` / ``_prefill_chunk_pages_fn`` quartet).  Token
        ``s`` of slot ``b`` is written at cache index ``offsets[b] + s``
        through the block table; positions are global prefix counts of the
        validity row, so RoPE phases match the contiguous executables
        exactly.  An offset of ``T`` parks an idle slot (writes drop,
        logits are garbage the caller ignores).

        The axes of the family:

        - ``toks [B, S]`` — ``S = 1`` is classic decode, ``S = k + 1`` the
          speculative verification chunk, ``S = Cc`` a prefill chunk;
        - ``apool``/``atables`` — per-slot LoRA deltas gathered from the
          adapter pool (``None`` = base model), composing with ANY ``S``:
          adapter-aware verify is the same code as adapter-aware decode;
        - the pool pytree — fp pairs or int8 six-tuples; the model's
          multi-token requantizing scatter makes spec × int8 the same code
          as single-token quantized decode;
        - ``paged_kernel`` — block-table-native ``ops.paged_attention``
          over the pool (shard_mapped at tp > 1) vs the gather path;
        - ``update_valid`` — decode/verify mark their tokens as new keys;
          chunked prefill pre-writes the FULL prompt's validity at
          admission (keys beyond the chunk are causally masked by the
          q-offset band), so its validity row passes through untouched;
        - ``last_only`` — decode/prefill sample from one position only
          (the last, or the traced row ``last_row`` when a prefill chunk is
          right-padded to its program's fixed width), and the head is
          applied to that row alone (:meth:`_apply_row`); a ``last_row``
          below 0 is a chunk that is not its prompt's last: nobody reads
          its logits, the head is not run and zeros come back.  Verify
          needs the whole ``[B, S, V]`` chunk of logits.

        Since every configuration is one parameterization of this single
        fn, the offset/validity/position math — the token-identity
        contract — exists exactly once, and feature pairs cannot diverge
        from their solo baselines."""
        S = toks.shape[1]
        T = valid.shape[1]
        idx = offsets[:, None] + jnp.arange(S)[None, :]  # [B, S] write indices
        with jax.named_scope("kv_valid"):
            if update_valid:
                hot = jnp.any(jnp.arange(T)[None, None, :] == idx[:, :, None],
                              axis=1)
                valid = jnp.where(hot, 1, valid)  # the new tokens become keys
            # valid keys strictly before
            counts = jnp.cumsum(valid, axis=1) - valid
            positions = jnp.take_along_axis(
                counts, jnp.clip(idx, 0, T - 1), axis=1)
        extra = {}
        if apool is not None:
            extra["adapters"] = self._gather_adapters(apool, atables)
        if paged_kernel:
            extra["paged_kernel"] = True
        if state_rows is not None:
            extra["state_rows"] = state_rows
        collect = (["moe_stats"] if self._moe else []) + (
            ["sparse_stats"] if self._sparse else [])
        args = (params, toks, positions.astype(jnp.int32), caches, offsets)
        kw = dict(kv_valid=valid, block_table=block_table,
                  mutable=collect or False, **extra)
        if last_only:
            logits, caches, stats = self._apply_row(*args, row=last_row, **kw)
        else:
            out = self.module.apply(*args, **kw)
            (logits, caches), stats = out if collect else (out, None)
        if self._moe:
            from neuronx_distributed_tpu.models.llama import moe_layer_stats

            return logits, caches, valid, moe_layer_stats(
                stats, self._moe_layers)
        if self._sparse:
            layers = stats["sparse_stats"]["model"]
            return logits, caches, valid, jnp.stack(
                [layers[f"layer_{i}"]["attn"]["chosen"][-1]
                 for i in self._sparse])
        return logits, caches, valid

    def _paged_phase(self, toks, offsets, block_table, caches, valid,
                     apool=None, atables=None, paged_kernel=None,
                     update_valid=True, last_only=True, last_row=None,
                     state_rows=None):
        """Compile-cache dispatcher for :meth:`_paged_step_fn`: every
        configuration jits the SAME underlying fn, keyed on its static
        parameterization — (chunk width, pool layout, batch rows, kernel
        flag, adapters, validity/logits mode).  The leading key component
        keeps the classic per-phase family names (``decode_pages`` /
        ``decode_pages_lora`` / ``verify_pages`` / ``prefill_chunk_pages``)
        so the compile ledger's per-family thrash detection keeps working —
        but a mixed spec × int8 × lora × chunked run now holds a handful of
        parameterizations of ONE program family, not four divergent code
        paths racing the LRU."""
        import functools as _ft

        self._serving_lru()
        toks = jnp.asarray(toks).astype(jnp.int32)
        if int(toks.shape[0]) == self.config.batch_size:
            # ONE signature whoever feeds a step its tokens: the host's put
            # is uncommitted, a token array that is a program's output (the
            # serve loop feeds a step the tokens of the step before it
            # where they lie) is committed — and an argument's commitment
            # is part of a jit's cache key
            toks = self._batch_committed(toks)
        valid = jnp.asarray(valid, jnp.int32)
        pk = self.paged_kernel if paged_kernel is None else bool(paged_kernel)
        lora = apool is not None
        name = ("prefill_chunk_pages" if not update_valid
                else "verify_pages" if not last_only
                else "decode_pages_lora" if lora else "decode_pages")
        key = (name, self._pool_tag(caches), int(toks.shape[1]),
               int(valid.shape[0]), pk, lora, update_valid, last_only,
               last_row is not None)
        fn = self._serving_cache.get(key)
        if fn is None:
            vout = (self._io_shardings["batch"](None)
                    if int(valid.shape[0]) == self.config.batch_size
                    else None)
            fn = jax.jit(
                _ft.partial(self._paged_step_fn, paged_kernel=pk,
                            update_valid=update_valid, last_only=last_only),
                donate_argnums=(4,),
                out_shardings=(None, self._pool_out_shardings(caches), vout)
                + ((None,) if self._moe or self._sparse else ()))
            fn = self._serving_cache.put(key, fn)
        args = (self.params, toks, jnp.asarray(offsets, jnp.int32),
                jnp.asarray(block_table, jnp.int32), caches, valid)
        if lora:
            args = args + (apool, jnp.asarray(atables, jnp.int32))
        kw = {}
        if last_row is not None:
            kw["last_row"] = jnp.int32(last_row)
        if self.recurrent:
            # which state row each batch row continues: its slot — a
            # decode's rows are the slots, a one-row chunk names its own
            if lora or not last_only:
                from neuronx_distributed_tpu.models.hybrid import (
                    RECURRENT_NAMES,
                )

                raise ValueError(
                    "LoRA pages and speculative verification are not "
                    f"carried through the recurrent ({RECURRENT_NAMES}) "
                    "layers")
            if state_rows is None:
                if int(toks.shape[0]) != self.config.batch_size:
                    raise ValueError(
                        "a paged program over fewer rows than slots must "
                        "be told its state rows (state_row=)")
                if not self._rows_in_place:
                    state_rows = np.arange(self.config.batch_size)
            if state_rows is not None:
                kw["state_rows"] = jnp.asarray(state_rows, jnp.int32)
        out = fn(*args, **kw)
        if self._moe:
            self.moe_seq += 1
            self._moe_stats.append({**out[3], "program": name,
                                    "seq": self.moe_seq})
        elif self._sparse:
            self._sparse_stats.append({"chosen": out[3], "program": name})
        return out[:3]

    def decode_pages(self, tok, offsets, block_table, caches, valid,
                     paged_kernel=None, state_rows=None):
        """Compiled paged per-slot decode step (page pool donated) — the
        ``S = 1`` member of the :meth:`_paged_step_fn` family.
        ``block_table`` is the ``[B, max_total_len // page_size]`` int32
        logical→physical page map; ``caches`` the pool pytree (fp pairs or
        the int8 six-tuples — each layout compiles its own program).
        ``paged_kernel`` (default: the model's resolved flag) selects the
        block-table-native kernel over the gather path; each value is its
        own cached program.  ``state_rows [B]`` (a model with recurrent
        layers only; default: row ``b`` continues state row ``b``) names the
        state row each batch row continues."""
        return self._paged_phase(tok, offsets, block_table, caches, valid,
                                 paged_kernel=paged_kernel,
                                 state_rows=state_rows)

    # -- multi-adapter (tenancy/) phase fns --------------------------------

    def make_adapter_pool(self, layout, num_pages: int):
        """Preallocated device adapter pool ``[num_pages, page_elems]``
        fp32, replicated over the mesh (adapters are tiny next to the KV
        pool; replication keeps the per-slot gather collective-free).
        ``layout`` is the :class:`~..tenancy.AdapterLayout` whose static
        factor offsets the gathered decode slices by; page 0 is the NULL
        page — its zeros ARE adapter 0's identity factors."""
        self._adapter_layout = layout
        pool = jnp.zeros((num_pages, layout.page_elems), jnp.float32)
        if model_parallel_is_initialized():
            pool = jax.device_put(pool, named_sharding(None, None))
        return pool

    def _write_adapter_page_fn(self, pool, block, phys):
        return jax.lax.dynamic_update_slice(
            pool, block[None, :].astype(pool.dtype), (phys, 0))

    def write_adapter_page(self, pool, block, phys_page):
        """Compiled adapter-page write (pool donated): one flattened
        ``[page_elems]`` host block lands in pool page ``phys_page`` (a
        traced scalar — one compiled program serves every load of every
        adapter)."""
        self._serving_lru()
        fn = self._serving_cache.get("write_adapter_page")
        if fn is None:
            fn = jax.jit(self._write_adapter_page_fn, donate_argnums=(0,))
            fn = self._serving_cache.put("write_adapter_page", fn)
        return fn(pool, jnp.asarray(block, jnp.float32),
                  jnp.int32(phys_page))

    def _gather_adapters(self, apool, atables):
        """Per-slot, per-layer gathered LoRA factors from the paged adapter
        pool: ONE gather ``apool[atables]`` pulls every slot's pages, then
        static slices carve the flat view into the layout's factors —
        ``[(a_q [B, H, r], b_q [B, r, NQ*D], a_v, b_v), ...]`` per layer.
        Slots on adapter 0 hold all-NULL tables, gather zeros, and add an
        exact zero delta."""
        layout = self._adapter_layout
        B = atables.shape[0]
        flat = apool[atables].reshape(B, -1)  # [B, AP * page_elems]
        out = []
        for layer_entries in layout.layer_entries():
            factors = []
            for _, off, shape in layer_entries:
                size = 1
                for d in shape:
                    size *= d
                factors.append(flat[:, off:off + size].reshape(B, *shape))
            out.append(tuple(factors))
        return out

    def decode_pages_lora(self, tok, offsets, block_table, caches, valid,
                          apool, atables, paged_kernel=None):
        """Compiled multi-adapter paged decode step (page pool donated) —
        the ``S = 1`` + adapters member of the :meth:`_paged_step_fn`
        family (one copy of the offsets/validity/position math), with
        per-slot LoRA deltas gathered from the adapter pool as one
        ``[B, r, d]`` einsum pair per targeted projection (S-LoRA's batched
        heterogeneous-adapter decode).  ``apool`` is the device adapter
        pool, ``atables`` the per-slot ``[B, adapter_pages]`` int32 page
        map (all-NULL rows = adapter 0 = exact no-op).  ``paged_kernel``
        as on :meth:`decode_pages` — the LoRA deltas land on q/v BEFORE
        the scatter/attend, so both paths see identical adapted
        projections."""
        return self._paged_phase(tok, offsets, block_table, caches, valid,
                                 apool=apool, atables=atables,
                                 paged_kernel=paged_kernel)

    def _context_lora_fn(self, params, ids, valid, apool, atable):
        """Single-request prefill with the request's LoRA adapter applied
        (``atable`` is the one-row ``[1, adapter_pages]`` page map) — the
        SAME :meth:`_context_fn` (one copy of the mask/position math); the
        adapter's deltas shape the prompt KV exactly as a merged dense
        model would, so per-adapter prefix pages are internally
        consistent."""
        return self._context_fn(
            params, ids, valid,
            adapters=self._gather_adapters(apool, atable))

    def prefill_one_lora(self, ids, valid, apool, atable):
        """Compiled adapter-aware single-request prefill — the tenancy
        counterpart of :meth:`prefill_one` (returns the same
        ``(logits [1, V], B=1 row caches)``), and like it the speculative
        DRAFT's only in the serving engine."""
        self._serving_lru()
        fn = self._serving_cache.get("prefill_one_lora")
        if fn is None:
            fn = jax.jit(self._context_lora_fn)
            fn = self._serving_cache.put("prefill_one_lora", fn)
        return fn(self.params, ids.astype(jnp.int32), valid, apool,
                  jnp.asarray(atable, jnp.int32))

    def prefill_chunk_pages(self, ids, offset, block_table, caches, valid,
                            apool=None, atables=None, paged_kernel=None,
                            last_row=None, state_row=None, want_logits=True):
        """Compiled paged chunk prefill (pool donated) — the ``S = Cc``,
        ``update_valid=False`` member of the :meth:`_paged_step_fn` family
        (Sarathi-style chunked prefill for the serving engine), lazily
        jitted per chunk width ``Cc`` so one program serves every chunk of
        that width at any offset of any slot.  ``ids [1, Cc]`` is the
        chunk's (padded) prompt slice, ``offset`` the scalar cache index
        its first token writes at, ``block_table [1, PP]`` the slot's
        logical→physical page map, ``valid [1, T]`` the slot's whole-cache
        key-validity row with the FULL prompt's (left-padded) validity
        pre-written and zeros beyond it: chunk token positions are global
        prefix counts of that mask, so RoPE phases match a one-shot
        context prefill exactly, and keys beyond the chunk are causally
        masked (q offset = cache offset) so the not-yet-written tail
        contributes nothing.  ``apool``/``atables`` prefill an adapter
        request's chunks with its LoRA deltas applied (the tenancy
        composition); ``paged_kernel`` walks the pool via the in-kernel
        chunked-prefill path instead of the O(T) gather.  ``last_row`` (a
        traced scalar) names the chunk row whose logits are wanted when the
        chunk is RIGHT-PADDED to a fixed program width: rows past it are
        either later prompt positions (written early, rewritten by their
        own chunk) or invalid cells past the prompt (never committed), so
        one compiled program serves every chunk of every prompt length.
        ``state_row`` (required for a model with recurrent layers, refused
        by none) is the slot whose state row the chunk continues: the
        program is one row wide and its block table says nothing of whose
        recurrent state it carries; a chunk that holds position 0 starts
        that row from zeros.  ``want_logits=False`` is a chunk that is not
        its prompt's last: the same compiled program (the flag is traced)
        writes the chunk's K/V and spends nothing on the head.
        Returns that row's logits ``[1, V]`` — the chunk's last position by
        default (the final chunk's are the prefill logits the first token
        samples from; the head is applied to that one row), ``None`` where
        none were wanted — and the updated pool."""
        logits, caches, _ = self._paged_phase(
            ids, jnp.asarray([offset], jnp.int32), block_table, caches,
            valid, apool=apool, atables=atables, paged_kernel=paged_kernel,
            update_valid=False, last_only=True,
            # no member of its own: the traced-row program, a row below 0
            last_row=last_row if want_logits else -1,
            state_rows=None if state_row is None or not self.recurrent
            else [int(state_row)])
        return (logits if want_logits else None), caches

    def verify_pages(self, toks, offsets, block_table, caches, valid,
                     apool=None, atables=None, paged_kernel=None):
        """Compiled batched speculative-verification step (page pool
        donated) — the ``S = k + 1``, ``last_only=False`` member of the
        :meth:`_paged_step_fn` family, lazily jitted per chunk width so one
        program serves every round at a given draft depth: token ``s`` of
        slot ``b`` is written at cache index ``offsets[b] + s`` (the
        model's multi-token block-table scatter — requantizing per page on
        int8 pools) and position ``i``'s logits judge the draft's proposal
        ``i+1`` — the shifted-logits verification trick.  An offset of
        ``T`` parks an idle slot (writes drop, logits are garbage the
        caller ignores).  ``apool``/``atables`` make the verify
        adapter-aware (spec × tenancy: the chunk is scored under each
        slot's OWN adapter, exactly as its solo decode would sample);
        ``paged_kernel`` as on :meth:`decode_pages`.  Returns
        ``(logits [B, S, V], caches, valid)``."""
        return self._paged_phase(toks, offsets, block_table, caches, valid,
                                 apool=apool, atables=atables,
                                 paged_kernel=paged_kernel, last_only=False)

    def _copy_page_fn(self, caches, src, dst):
        def cp(c):
            # 4-D page payloads and 1-D per-page quant params alike: copy
            # row `src` of the leading page axis to row `dst`
            row = jax.lax.dynamic_slice_in_dim(c, src, 1, axis=0)
            return jax.lax.dynamic_update_slice(
                c, row, (dst,) + (0,) * (c.ndim - 1))

        return jax.tree.map(cp, caches)

    def copy_page(self, caches, src_page, dst_page):
        """Compiled pool-internal page copy (pool donated) — the device half
        of the allocator's copy-on-write: duplicate a shared page before
        writing the copy."""
        from neuronx_distributed_tpu.kvcache.pool import page_kinds

        if self.recurrent or self._sparse or len(page_kinds(
                getattr(self.module, "config", None))) > 1:
            raise ValueError(
                "copy_page duplicates a page of every layer: a layer list "
                "with state rows or compressed keys, or layers whose pages "
                "come in several kinds, has no shared pages to copy (prefix "
                "sharing is off for it)")
        self._serving_lru()
        key = ("copy_page", self._pool_tag(caches))
        fn = self._serving_cache.get(key)
        if fn is None:
            fn = jax.jit(self._copy_page_fn, donate_argnums=(0,),
                         out_shardings=self._pool_out_shardings(caches))
            fn = self._serving_cache.put(key, fn)
        return fn(caches, jnp.int32(src_page), jnp.int32(dst_page))

    def _insert_valid_fn(self, valid, row_valid, slot):
        with jax.named_scope("kv_valid"):
            return jax.lax.dynamic_update_slice_in_dim(
                valid, row_valid, slot, axis=0)

    def insert_valid(self, valid, row_valid, slot):
        """Compiled validity-row insert (donated) — all an admission writes
        outside the page pool: block tables carry the KV, so only the
        validity row needs writing."""
        self._serving_lru()
        fn = self._serving_cache.get("insert_valid")
        if fn is None:
            fn = jax.jit(self._insert_valid_fn, donate_argnums=(0,),
                         out_shardings=self._io_shardings["batch"](None))
            fn = self._serving_cache.put("insert_valid", fn)
        return fn(self._batch_committed(valid),
                  jnp.asarray(row_valid, jnp.int32), jnp.int32(slot))

    def _build(self):
        from jax.sharding import NamedSharding

        def sds(x):
            # carry mesh shardings into the AOT signature — compiled
            # executables are strict about argument placement
            sh = getattr(x, "sharding", None)
            sh = sh if isinstance(sh, NamedSharding) else None
            return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x), sharding=sh)

        cfg = self.config
        B, C, T = cfg.batch_size, cfg.context_len, cfg.max_total_len

        # Pin the batch-dim sharding of every array that loops BETWEEN
        # executables (tokens, validity masks, logits, caches).  AOT programs
        # are strict about committed-argument placement, and without pinning
        # the compiler is free to choose e.g. a replicated cache output from
        # `context` while `decode` was compiled expecting a dp-sharded cache
        # input — a guaranteed mismatch the moment dp > 1.  Policy matches
        # init_kv_caches: batch over dp when divisible, else replicated.
        if model_parallel_is_initialized():
            from jax.sharding import PartitionSpec as P

            mesh = get_mesh()
            bax = _serving_batch_axes(B)

            def bsh(*rest):
                return NamedSharding(mesh, P(bax, *rest))
        else:
            def bsh(*rest):
                return None

        def bsds(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=bsh(*(None,) * (len(shape) - 1)))

        ids_spec = bsds((B, C))
        vctx_spec = bsds((B, C))
        tok_spec = bsds((B, 1))
        off_spec = jax.ShapeDtypeStruct((), jnp.int32)
        valid_spec = bsds((B, T))
        cache_sh = _kv_cache_sharding(B, self.num_kv_heads)
        cache_leaf = jax.ShapeDtypeStruct(
            (B, T, self.num_kv_heads, self.head_dim), cfg.kv_cache_dtype,
            sharding=cache_sh)
        cache_spec = [(cache_leaf, cache_leaf)] * self.num_layers
        cache_out = [(cache_sh, cache_sh)] * self.num_layers
        params_spec = jax.tree.map(sds, self.params)
        # keep the jitted phase fns: the export path reuses them (their
        # lowering cache) instead of re-jitting from scratch.
        # logits never re-enter an AOT program (they go straight to eager
        # argmax/sampling), so their sharding stays unconstrained — pinning
        # them would force a full-vocab all-gather off the tp-split lm_head
        self._context_jit = jax.jit(
            self._context_fn, out_shardings=(None, cache_out)
        )
        self._decode_jit = jax.jit(
            self._decode_fn, donate_argnums=(3,),
            out_shardings=(None, cache_out, bsh(None)),
        )
        # The contiguous executables compile on FIRST USE, not here: a paged
        # serving engine never calls them, and at real widths the batched
        # [B, C] x [B, T] context program (dense scores over the whole
        # padded batch) is one the chip's compiler refuses for memory —
        # compiling it eagerly made every ParallelInferenceModel at such a
        # size unconstructible for the sake of a program nothing would run.
        self._aot_lowerings = {
            "context": lambda: self._context_jit.lower(
                params_spec, ids_spec, vctx_spec),
            # donated caches (arg 3) → in-place KV update
            "decode": lambda: self._decode_jit.lower(
                params_spec, tok_spec, off_spec, cache_spec, valid_spec),
        }
        self._aot_compiled = {}
        self._io_shardings = {
            "batch": bsh, "cache_out": cache_out,
        }
        if cfg.chunked_prefill:
            self._prefill_chunk_jit = jax.jit(
                self._prefill_chunk_fn, donate_argnums=(3,),
                out_shardings=(None, cache_out),
            )
            self._aot_lowerings["prefill_chunk"] = (
                lambda: self._prefill_chunk_jit.lower(
                    params_spec, ids_spec, off_spec, cache_spec, valid_spec))
        self._loop_cache = _CompiledLRU("decode_loop", owner=self)
        self._serving_lru(reset=True)
        self._arg_specs = (
            params_spec, ids_spec, vctx_spec, tok_spec, off_spec, cache_spec,
            valid_spec,
        )

    def _aot(self, family: str):
        """The AOT executable of a contiguous phase fn, compiled on first
        use and ledger-timed (the compile ledger's "aot" rows, with
        cost/memory stats off the executable)."""
        compiled = self._aot_compiled.get(family)
        if compiled is None:
            cfg = self.config
            lowered = self._aot_lowerings[family]()
            t0 = time.perf_counter()
            compiled = lowered.compile()
            if self.compile_ledger is not None:
                self.compile_ledger.record_compile(
                    family,
                    (cfg.batch_size, cfg.context_len, cfg.max_total_len),
                    (time.perf_counter() - t0) * 1e3,
                    kind="aot", compiled=compiled)
            self._aot_compiled[family] = compiled
        return compiled

    @property
    def context(self):
        return self._aot("context")

    @property
    def decode(self):
        return self._aot("decode")

    @property
    def prefill_chunk(self):
        if "prefill_chunk" not in self._aot_lowerings:
            # hasattr() is how callers ask whether chunking was traced
            raise AttributeError(
                "no chunk-prefill executable: built without "
                "InferenceConfig(chunked_prefill=True)")
        return self._aot("prefill_chunk")


def speculative_generate(
    target: "ParallelInferenceModel",
    draft: "ParallelInferenceModel",
    prompt_ids: jax.Array,
    max_new_tokens: int,
    k: int = 4,
    prompt_lens: Optional[jax.Array] = None,
    return_stats: bool = False,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    rng: Optional[jax.Array] = None,
):
    """Speculative decoding: a small draft model proposes ``k`` tokens per
    round and the target verifies them in ONE chunked forward.  Per-round
    host sync replaces per-token host sync, and the target runs
    ``ceil(n / (accepted+1))`` chunk forwards instead of ``n`` single-token
    steps — the serving win when the draft is much smaller.

    ``temperature == 0`` (default): greedy — accept while the target's
    argmax agrees; the first disagreement is replaced by the target's token,
    and a fully-accepted round yields the target's bonus token.  The output
    is PROVABLY identical to the target's own greedy decode.

    ``temperature > 0`` (with the same ``top_k``/``top_p`` knobs as
    ``generate``): the standard accept/reject sampler (Leviathan et al.) —
    proposals accepted with prob ``min(1, p/q)``, rejections resampled from
    the residual ``norm(max(p - q, 0))`` — whose outputs are distributed
    EXACTLY as the target's own sampler.  Token-index rng keys match
    ``generate``'s stream, so with ``draft == target`` the sampled output is
    bit-identical to plain sampled generation (the positive control the
    tests pin).

    ``target``/``draft`` must share the tokenizer and serving shapes
    (``batch_size``, ``context_len``, ``max_total_len``).  Rejected cache
    slots are never rewound: they sit at indices >= the next write offset,
    index-based causal masking hides them, and the next round's chunk write
    overwrites them before any query can attend that far.

    Capability beyond the reference (whose serving is plain per-token
    HF-generate driving, ``neuron_modeling_llama.py:437-465``).
    """
    tcfg, dcfg = target.config, draft.config
    for f in ("batch_size", "context_len", "max_total_len"):
        if getattr(tcfg, f) != getattr(dcfg, f):
            raise ValueError(
                f"target/draft serving shapes differ on {f}: "
                f"{getattr(tcfg, f)} vs {getattr(dcfg, f)}"
            )
    tv = getattr(getattr(target, "module", None), "config", None)
    dv = getattr(getattr(draft, "module", None), "config", None)
    if tv is not None and dv is not None and getattr(tv, "vocab_size", None) != getattr(dv, "vocab_size", None):
        raise ValueError(
            f"target/draft vocab_size differ ({tv.vocab_size} vs {dv.vocab_size}): "
            "speculative decoding needs one shared tokenizer — out-of-range "
            "proposals would be silently clamped, not rejected"
        )
    B, C = prompt_ids.shape
    T = tcfg.max_total_len
    if (B, C) != (tcfg.batch_size, tcfg.context_len):
        raise ValueError(
            f"prompt shape {(B, C)} does not match traced shape "
            f"{(tcfg.batch_size, tcfg.context_len)}"
        )
    if C + max_new_tokens > T:
        # the final round clips kk to the remaining budget, so the largest
        # write index is C + max_new_tokens - 1 — the same bound as generate()
        raise ValueError(
            f"context {C} + new {max_new_tokens} exceeds max_total_len {T}"
        )
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    sampling = not (isinstance(temperature, (int, float)) and float(temperature) == 0.0)
    if sampling and rng is None:
        raise ValueError("temperature sampling requires an rng key")
    # token-index keys match generate()'s fold_in(rng, i) stream, so with
    # draft == target the sampled output is bit-identical to plain sampling;
    # accept coins and residual resampling use salted sub-streams (the same
    # salts as the serving engine's batched draft-k-verify)
    _ACC, _RES = SPEC_ACCEPT_SALT, SPEC_RESIDUAL_SALT

    valid_ctx = target._valid_ctx(prompt_lens)
    tail = jnp.zeros((B, T - C), jnp.int32)
    valid_t = jnp.concatenate([valid_ctx, tail], axis=1)
    valid_d = valid_t

    logits_t, caches_t = target.context(target.params, prompt_ids.astype(jnp.int32), valid_ctx)
    _, caches_d = draft.context(draft.params, prompt_ids.astype(jnp.int32), valid_ctx)

    if sampling:
        first = _sample_logits(logits_t, jax.random.fold_in(rng, 0),
                               temperature, top_k, top_p)
    else:
        first = jnp.argmax(logits_t, axis=-1).astype(jnp.int32)
    committed = [first[:, None]]
    n_done = 1
    offset = C  # cache index of the next write; committed[-1] not yet written
    rounds = proposed_total = accepted_total = 0

    while n_done < max_new_tokens:
        kk = min(k, max_new_tokens - n_done)
        # --- draft proposes kk tokens (its decode also ingests committed[-1])
        proposals = []
        q_filtered = []
        tok = committed[-1]
        vd = valid_d
        for j in range(kk):
            dlogits, caches_d, vd = draft.decode(
                draft.params, tok, jnp.int32(offset + j), caches_d, vd
            )
            if sampling:
                qf = _filtered_logits(dlogits, temperature, top_k, top_p)
                q_filtered.append(qf)
                nxt = jax.random.categorical(
                    jax.random.fold_in(rng, n_done + j), qf, axis=-1
                ).astype(jnp.int32)
            else:
                nxt = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
            tok = nxt[:, None]
            proposals.append(tok)
        props = jnp.concatenate(proposals, axis=1)  # [B, kk]

        # --- target verifies the whole round in one chunk forward
        chunk = jnp.concatenate([committed[-1], props], axis=1)  # [B, kk+1]
        logits_full, caches_t, valid_t = target.score_chunk(
            chunk, offset, caches_t, valid_t
        )

        if sampling:
            # Leviathan et al. accept/reject: accept x ~ q with prob
            # min(1, p(x)/q(x)); the first rejection resamples from the
            # residual norm(max(p - q, 0)).  Lockstep: the batch advances by
            # the MINIMUM acceptance; rows cut before their own rejection
            # discard their coin and resample that position directly from p
            # (both are exact draws from p).
            pf = _filtered_logits(logits_full, temperature, top_k, top_p)
            p_probs = jax.nn.softmax(pf[:, :kk], axis=-1)  # [B, kk, V]
            q_probs = jax.nn.softmax(jnp.stack(q_filtered, axis=1), axis=-1)
            px = jnp.take_along_axis(p_probs, props[..., None], axis=-1)[..., 0]
            qx = jnp.take_along_axis(q_probs, props[..., None], axis=-1)[..., 0]
            u = jax.random.uniform(
                jax.random.fold_in(jax.random.fold_in(rng, _ACC), n_done), (B, kk)
            )
            accept = np.asarray(u < jnp.minimum(1.0, px / jnp.maximum(qx, 1e-20)))
            lead = np.minimum.accumulate(accept, axis=1)
            j = int(lead.all(axis=0).sum())
            take = min(j + 1, max_new_tokens - n_done)
            for i in range(min(take, j)):
                committed.append(props[:, i:i + 1])
            if take == j + 1:  # corrective / bonus position
                if j == kk:  # full accept: bonus straight from p_{kk}
                    nxt = jax.random.categorical(
                        jax.random.fold_in(rng, n_done + kk), pf[:, kk], axis=-1
                    ).astype(jnp.int32)
                else:
                    res = jnp.maximum(p_probs[:, j] - q_probs[:, j], 0.0)
                    res_sum = jnp.sum(res, axis=-1, keepdims=True)
                    # rows whose own coin chain was still accepting at j draw
                    # from p directly; degenerate all-zero residuals (p <= q
                    # everywhere off the sample) also fall back to p
                    rejected = jnp.asarray(~lead[:, j])[:, None]
                    use_res = jnp.logical_and(rejected, res_sum > 0)
                    dist = jnp.where(use_res, res / jnp.maximum(res_sum, 1e-20),
                                     p_probs[:, j])
                    nxt = jax.random.categorical(
                        jax.random.fold_in(
                            jax.random.fold_in(rng, _RES), n_done + j),
                        jnp.log(jnp.maximum(dist, 1e-20)), axis=-1,
                    ).astype(jnp.int32)
                committed.append(nxt[:, None])
        else:
            tgt = jnp.argmax(logits_full, axis=-1).astype(jnp.int32)  # [B, kk+1]

            # leading agreement across the batch (lockstep: the whole batch
            # advances by the minimum acceptance, keeping one shared offset)
            agree = np.asarray(tgt[:, :kk] == props)  # host sync, once per round
            lead = np.minimum.accumulate(agree, axis=1)
            j = int(lead.all(axis=0).sum())  # tokens accepted this round

            take = min(j + 1, max_new_tokens - n_done)  # proposals then a target token
            for i in range(take - 1):
                committed.append(props[:, i:i + 1])
            # tgt[:, take-1] is t_{take}: the corrective/bonus token when
            # take == j+1, and (== p_take) the clipped final token otherwise
            committed.append(tgt[:, take - 1:take])
        if take == kk + 1:
            # full accept: the draft proposed p_kk but never WROTE it (its
            # last decode produced it); the slot now lies inside the
            # committed region where nothing will overwrite it, so ingest it
            # — one extra draft step, only on fully-accepted rounds
            _, caches_d, vd = draft.decode(
                draft.params, props[:, kk - 1:kk], jnp.int32(offset + kk),
                caches_d, vd,
            )
        n_done += take
        offset += take
        # draft follows the same offset; its stale slots (> offset) are
        # overwritten next round, and its valid mask matches the target's
        valid_d = valid_t
        rounds += 1
        proposed_total += kk
        # verdict-level agreement (j <= kk): a proposal that agreed but fell
        # past max_new_tokens was not *rejected* — the rate measures draft
        # quality, not the output-length clip
        accepted_total += j

    out = jnp.concatenate([prompt_ids] + committed, axis=1)
    if return_stats:
        return out, {
            "rounds": rounds,
            "proposed": proposed_total,
            "accepted": accepted_total,
            "acceptance_rate": accepted_total / max(proposed_total, 1),
        }
    return out
