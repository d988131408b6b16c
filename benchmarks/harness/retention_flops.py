"""Operations and bytes of a power-retention layer's CORE (degree 2) — the
yardstick's own arithmetic, whatever form computes it.

Counted work is the LAYER's, not a kernel's.  A token at position ``n`` (it
sees ``n`` tokens, itself included) can be served exactly in two ways: the
quadratic form — ``q . k`` and ``a v`` against each token seen, ``4 n d`` a
query head — or the state form at the MINIMAL width of the symmetric square,
``D = d (d + 1) / 2`` (8,256 at ``d = 128``): the read ``phi(q) S``, ``2 D d``
a query head, and the update ``phi(k) v^T``, ``2 D d`` a key/value head.  A
token counts the cheaper of the two, so that no later implementation — tiles
of the symmetric square, a quadratic start below the crossover, a switch-over
— can read over 100% of the compute roofline.

A decode step's least traffic is ONE read of the state of every row it
steps: ``kv heads x D x d`` float32 and the normaliser ``kv heads x D``, at
the minimal ``D`` — the least any exact step does.  A step that reads and
writes the state reads at most 50% of that roofline; one that folds its
writes a chunk at a time may approach 100 and cannot pass it.

Keys are the published ``config.json`` names, read from the cell's
configuration file.
"""

from __future__ import annotations

STATE_BYTES = 4    # the recurrent state is float32


def _dims(cfg: dict):
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def phi_dim_min(d: int) -> int:
    """The columns of the symmetric square of ``d`` channels, exactly."""
    return d * (d + 1) // 2


def token_flops(n: float, cfg: dict) -> float:
    """The least operations of one layer's core for ONE token at position
    ``n`` (1 = the sequence's first)."""
    nq, nkv, d = _dims(cfg)
    return min(4.0 * n * d * nq, 2.0 * phi_dim_min(d) * d * (nq + nkv))


def crossover(cfg: dict) -> float:
    """The position past which the state form is the cheaper."""
    nq, nkv, d = _dims(cfg)
    return 2.0 * phi_dim_min(d) * d * (nq + nkv) / (4.0 * d * nq)


def chunk_flops(first: int, tokens: int, cfg: dict) -> float:
    """One layer's core over ``tokens`` tokens, the first at position
    ``first`` (1-based)."""
    x = crossover(cfg)
    nq, nkv, d = _dims(cfg)
    last = first + tokens - 1
    below = max(min(last, int(x)) - first + 1, 0)      # quadratic tokens
    quad = 4.0 * d * nq * (first + first + below - 1) * below / 2.0
    return quad + (tokens - below) * 2.0 * phi_dim_min(d) * d * (nq + nkv)


def step_bytes(rows: float, cfg: dict) -> float:
    """The least bytes of one layer's decode step over ``rows`` rows: each
    row's state and normaliser, read once."""
    _, nkv, d = _dims(cfg)
    D = phi_dim_min(d)
    return rows * nkv * (D * d + D) * STATE_BYTES
