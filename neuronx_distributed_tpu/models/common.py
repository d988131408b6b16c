"""Shared model-building blocks across the model families."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.parallel.layers import shard_activation
from neuronx_distributed_tpu.parallel.loss import parallel_cross_entropy
from neuronx_distributed_tpu.parallel.mesh import TENSOR_AXES


def maybe_remat(block_cls, remat: str, static_argnums: Tuple[int, ...] = ()):
    """Apply the configured rematerialization mode to a transformer block
    class.  'full' recomputes everything in bwd; 'selective' saves matmul
    outputs (the XLA analogue of the reference checkpointing
    CoreAttention+MLP only, ``modeling_llama_nxd.py:184-214``).

    ``static_argnums`` indexes ``__call__``'s python-static args counting the
    module itself as arg 0 (flax's convention)."""
    if remat not in ("none", "selective", "full"):
        raise ValueError(f"unknown remat mode {remat!r}")
    if remat == "none":
        return block_cls
    policy = (
        None
        if remat == "full"
        else jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    )
    return nn.remat(block_cls, policy=policy, prevent_cse=False,
                    static_argnums=static_argnums)


MOE_AUX_COEF = 0.01  # Switch-Transformer load-balancing coefficient


def _causal_lm_loss_parts(module, params, batch, rng=None):
    """Shared body of the two loss entry points: returns
    ``(masked_loss_sum, unmasked_token_count, aux_mean_or_None)``."""
    import inspect

    accepted = inspect.signature(type(module).__call__).parameters
    kwargs = {}
    for key in ("positions", "segment_ids"):
        if batch.get(key) is not None:
            if key not in accepted:
                raise TypeError(
                    f"batch carries {key!r} but {type(module).__name__} does "
                    "not accept it; drop the key or use a packing-aware model"
                )
            kwargs[key] = batch[key]
    logits, variables = module.apply(params, batch["ids"], mutable=["losses"], **kwargs)
    labels = batch["labels"]
    per_tok = parallel_cross_entropy(logits, labels)
    mask = batch.get("mask")
    if mask is None:
        mask = (labels >= 0).astype(jnp.float32)
    else:
        mask = mask.astype(jnp.float32) * (labels >= 0)
    loss_sum = jnp.sum(per_tok * mask)
    tok = jnp.sum(mask)
    aux_terms = jax.tree.leaves(variables.get("losses", {}))
    aux = jnp.mean(jnp.stack(aux_terms)) if aux_terms else None
    return loss_sum, tok, aux


def moe_step_stats(variables) -> dict:
    """What a TRAIN step hands on of the ``moe_stats`` collection its routed
    blocks sowed (``parallel/moe.py``, dropless path), stacked over the
    routed layers in their order: ``{"moe_load": [L, E]}``, the valid
    assignments each held expert took, and, where the layers hold a share
    of their experts, ``"moe_assigned" [L]``, the valid assignments held or
    not, and, where such a share passes over the rows it holds,
    ``"moe_computed" [L]``, the rows the spans that ran passed over.  ``{}``
    for a model that sows none.  Each row's choice, ``[tokens, K]`` a layer,
    stays behind (``models.llama.moe_layer_stats`` reads it
    from the collection for a check)."""
    import re

    from flax import traverse_util

    flat = traverse_util.flatten_dict(dict(variables.get("moe_stats", {})))

    def layer(path):
        return [int(n) for p in path for n in re.findall(r"\d+", p)]

    out = {}
    for key, name in (("load", "moe_load"), ("assigned", "moe_assigned"),
                      ("computed", "moe_computed")):
        rows = [v[-1] for p, v in sorted(flat.items(), key=lambda kv: layer(
            kv[0])) if p[-1] == key]
        if rows:
            out[name] = jnp.stack(rows)
    return out


def causal_lm_loss(module, params, batch, rng=None) -> jax.Array:
    """Next-token loss over vocab-sharded logits; ``batch = {ids, labels[,
    mask]}``, labels < 0 (ignore convention) drop out of the mean.  Works for
    any causal-LM module whose ``apply(params, ids)`` returns logits.

    MoE models (``num_experts > 1``) sow per-layer load-balancing terms into
    the ``losses`` collection; they are averaged and added here with
    ``MOE_AUX_COEF`` (dense models sow nothing — zero overhead).

    Packed batches (``data.packing``) may carry ``positions`` (per-document
    RoPE phases) and ``segment_ids`` (cross-document attention blocking);
    both are forwarded when the module accepts them (the Llama family does)."""
    loss_sum, tok, aux = _causal_lm_loss_parts(module, params, batch, rng)
    loss = loss_sum / jnp.maximum(tok, 1.0)
    if aux is not None:
        loss = loss + MOE_AUX_COEF * aux
    return loss


def causal_lm_loss_sum(module, params, batch, rng=None):
    """Token-sum form of :func:`causal_lm_loss`: returns ``(loss_sum, tok)``
    so callers can normalize by the *global* unmasked-token count.

    ``make_train_step`` recognizes the 2-tuple return and accumulates
    ``(sum, tok)`` across grad-accum microbatches, making the optimizer
    update the exact token-masked global mean even when microbatches carry
    unequal numbers of unmasked tokens — the caveat the plain mean-of-means
    path documents (the PP engine already normalizes this way).

    MoE aux terms are folded in as ``aux_mean * tok`` so that
    ``loss_sum / tok`` equals :func:`causal_lm_loss` exactly on a single
    batch; under accumulation the aux becomes the token-weighted mean of
    per-microbatch aux means (vs. the unweighted mean of the mean-of-means
    path — both are estimators of the same per-batch balance statistic)."""
    loss_sum, tok, aux = _causal_lm_loss_parts(module, params, batch, rng)
    if aux is not None:
        loss_sum = loss_sum + MOE_AUX_COEF * aux * tok
    return loss_sum, tok


def make_causal_lm_loss_sum(chunk_size: int = 0):
    """Factory for a ``(loss_sum, tok)`` causal-LM loss with an optionally
    *chunked* head: with ``chunk_size > 0`` the lm-head matmul and the
    cross entropy run per sequence chunk inside a rematerialized
    ``lax.scan``, so the full ``[B, S, V]`` logits — and the fp32 softmax
    residuals autodiff would otherwise save for backward — never exist in
    HBM.  Peak loss-head memory drops from O(B·S·V) to O(B·chunk·V) at the
    cost of recomputing the head matmul in backward (~2·B·S·H·V extra FLOPs,
    a few percent of a training step).

    The reference cannot express this (its loss consumes materialized logits,
    ``parallel_layers/loss_functions.py:17-135``); on TPU the [B,S,V] buffer
    is the single biggest activation of the whole step and the prime
    HBM-pressure suspect at bench shapes (VERDICT r3 #1c).

    Requires a module exposing the ``hidden(ids, ...)`` / ``head(h)`` method
    pair (the Llama family does); ``chunk_size == 0`` falls back to the
    plain :func:`causal_lm_loss_sum`.

    A model whose routed blocks sow ``moe_stats`` gets a third value,
    :func:`moe_step_stats`: ``make_train_step`` hands it on in the step's
    metrics (summed over the microbatches of an accumulated step), and
    ``fit()`` fetches it with the loss."""
    if chunk_size == 0:
        return causal_lm_loss_sum

    def loss_fn(module, params, batch, rng=None):
        import inspect
        import math

        accepted = inspect.signature(type(module).hidden).parameters
        kwargs = {}
        for key in ("positions", "segment_ids"):
            if batch.get(key) is not None:
                if key not in accepted:
                    raise TypeError(
                        f"batch carries {key!r} but {type(module).__name__}."
                        "hidden does not accept it"
                    )
                kwargs[key] = batch[key]
        h, variables = module.apply(
            params, batch["ids"], mutable=["losses", "moe_stats"],
            method="hidden", **kwargs
        )
        labels = batch["labels"]
        mask = batch.get("mask")
        if mask is None:
            mask = (labels >= 0).astype(jnp.float32)
        else:
            mask = mask.astype(jnp.float32) * (labels >= 0)

        B, S = labels.shape
        # largest divisor of S that is <= chunk_size (NOT gcd — gcd(2048,
        # 1000)=8 would silently scan 256 tiny chunks)
        c = next(d for d in range(min(chunk_size, S), 0, -1) if S % d == 0)
        n = S // c

        def chunk_fn(p, h_c, y_c, m_c):
            logits = module.apply(p, h_c, method="head")
            per_tok = parallel_cross_entropy(logits, y_c)
            return jnp.sum(per_tok * m_c), jnp.sum(m_c)

        # remat: backward recomputes the chunk's logits from (params, h_c)
        # instead of saving softmax residuals per chunk
        chunk_fn = jax.checkpoint(chunk_fn)

        def body(carry, xs):
            h_c, y_c, m_c = xs
            ls, tok = chunk_fn(params, h_c, y_c, m_c)
            return (carry[0] + ls, carry[1] + tok), None

        # the chunk scan, the head matmul in it and its recomputation are
        # one group in the device trace
        with jax.named_scope("loss_head"):
            xs = (
                h.reshape(B, n, c, h.shape[-1]).swapaxes(0, 1),
                labels.reshape(B, n, c).swapaxes(0, 1),
                mask.reshape(B, n, c).swapaxes(0, 1),
            )
            (loss_sum, tok), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)), xs
            )
        aux_terms = jax.tree.leaves(variables.get("losses", {}))
        if aux_terms:
            loss_sum = loss_sum + MOE_AUX_COEF * jnp.mean(jnp.stack(aux_terms)) * tok
        stats = moe_step_stats(variables)
        return (loss_sum, tok, stats) if stats else (loss_sum, tok)

    return loss_fn


def dense_mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
) -> jax.Array:
    """Multi-head attention core, ``q/k/v [B, S, N, D]`` with heads sharded
    over the TP axes (each shard computes its own heads, no collective —
    the layout the reference's per-rank ``CoreAttention`` computes on,
    ``examples/training/tp_dp_bert_hf_pretrain/tp_dp_bert_large_hf_pretrain_hdf5.py:419``).

    ``mask``: optional boolean, broadcastable to ``[B, N, S, T]``, True =
    attend.  fp32 softmax regardless of input dtype.
    """
    B, S, N, D = q.shape
    T = k.shape[1]
    q = shard_activation(q, P(P.UNCONSTRAINED, None, TENSOR_AXES, None))
    scores = jnp.einsum("bsnd,btnd->bnst", q, k, preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(D).astype(jnp.float32)
    if causal:
        cmask = jnp.arange(T)[None, :] <= jnp.arange(S)[:, None] + (T - S)
        scores = jnp.where(cmask[None, None], scores, jnp.finfo(jnp.float32).min)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", probs, v, preferred_element_type=q.dtype)


def build_pipelined_causal_lm(
    *,
    embed_mod,
    block_mod,
    head_mod,
    block_fn,
    num_layers: int,
    max_seq_len: int,
    hidden_size: int,
    dtype,
    remat: str,
    sequence_parallel: bool,
    num_microbatches: int,
    seed: int = 0,
    schedule: str = "1f1b",
    pipeline_cuts=None,
    block_aux: bool = False,
    extra_keys=(),
    num_chunks: int = 1,
):
    """Shared engine wiring for pipeline-parallel causal-LM families.

    A family supplies its three modules and a ``block_fn(layer_params, x) ->
    y`` (or ``(y, aux)`` with ``block_aux``); everything else — the
    vocab-parallel head loss, init thunks, remat-policy mapping, SP
    activation spec — is identical across families and lives here so an
    engine-protocol change lands once (contrast the reference, where each
    example port re-implements its trainer wiring)."""
    import neuronx_distributed_tpu.pipeline.engine as engine
    from neuronx_distributed_tpu.parallel.layers import trailing_spec
    from neuronx_distributed_tpu.parallel.mesh import SEQUENCE_AXES, get_mesh

    mesh = get_mesh()

    def embed_fn(ep, ids):
        return embed_mod.apply({"params": ep}, ids)

    def head_fn(hp, h):
        return head_mod.apply({"params": hp}, h)

    def head_loss_fn(hp, h, labels):
        logits = head_fn(hp, h)
        per_tok = parallel_cross_entropy(logits, labels)
        mask = (labels >= 0).astype(jnp.float32)
        return jnp.sum(per_tok * mask), jnp.sum(mask)

    return engine.build_pipelined_model(
        embed_fn=embed_fn,
        block_fn=block_fn,
        head_loss_fn=head_loss_fn,
        head_fn=head_fn,
        embed_init=lambda r: embed_mod.init(r, jnp.zeros((1, max_seq_len), jnp.int32)),
        block_init=lambda r: block_mod.init(
            r,
            jnp.zeros((1, max_seq_len, hidden_size), dtype),
            jnp.zeros((1, max_seq_len), jnp.int32),
        ),
        head_init=lambda r: head_mod.init(
            r, jnp.zeros((1, max_seq_len, hidden_size), dtype)
        ),
        num_layers=num_layers,
        num_microbatches=num_microbatches,
        mesh=mesh,
        remat_block=remat != "none",
        remat_policy=(
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            if remat == "selective"
            else None
        ),
        seed=seed,
        schedule=schedule,
        act_spec=(
            trailing_spec(3, seq=SEQUENCE_AXES, last=None)
            if sequence_parallel
            else None
        ),
        block_aux=block_aux,
        pipeline_cuts=pipeline_cuts,
        extra_keys=extra_keys,
        num_chunks=num_chunks,
    )
