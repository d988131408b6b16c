"""Operations and bytes of a routed expert block — the yardstick's own
arithmetic for the grouped matmuls of a mixture of SwiGLU experts.

An ASSIGNMENT is one (token, expert) pair: a token routed to ``K`` experts
makes ``K`` of them, and each is one row of the gate-up and of the down
grouped matmul.  Operations count the assignments (what the mathematics
requires: no capacity padding, no expert multiplying a row it was not
given); bytes count the weights of the experts HIT — an expert no row chose
is never read — plus the rows in and out.

Keys are the published ``config.json`` names (``hidden_size``,
``intermediate_size`` = the width of ONE expert, ``num_experts``,
``num_experts_per_tok``), read from the cell's configuration file.
"""

from __future__ import annotations

from typing import Optional

from benchmarks.harness import flops


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"])


def expected_experts_hit(rows: int, cfg: dict) -> float:
    """Experts with at least one row when ``rows`` tokens each choose ``K``
    distinct experts of ``E`` uniformly: ``E (1 - (1 - K/E)^rows)``.  The
    trace does not say which experts one call hit; a seeded random router
    is near uniform (the counter ``moe/expert_load_max_over_mean`` says how
    near), and a skewed one hits FEWER, so this never understates the
    bytes by more than the skew."""
    _, _, E, K = _dims(cfg)
    return E * (1.0 - (1.0 - K / E) ** rows)


def grouped_matmul_flops(assignments: float, cfg: dict) -> float:
    """Gate, up and down of every assignment: 2 x 3 x H x F each."""
    H, F, _, _ = _dims(cfg)
    return 2.0 * assignments * 3 * H * F


def grouped_matmul_bytes(assignments: float, experts_hit: float, cfg: dict,
                         weight_bytes: int = 2, act_bytes: int = 2) -> float:
    """Least HBM traffic of the two grouped matmuls of one expert block:
    the gate-up and down weights of the experts hit, once; the gate-up
    reads ``[A, H]`` and writes ``[A, 2F]``, the down reads ``[A, F]`` and
    writes ``[A, H]``."""
    H, F, _, _ = _dims(cfg)
    return (experts_hit * 3.0 * H * F * weight_bytes
            + assignments * (2.0 * H + 3.0 * F) * act_bytes)


def router_flops(rows: float, cfg: dict) -> float:
    H, _, E, _ = _dims(cfg)
    return 2.0 * rows * H * E


def router_bytes(rows: float, cfg: dict, weight_bytes: int = 2,
                 act_bytes: int = 2) -> float:
    """The router's weights, the rows read, the fp32 probabilities out."""
    H, _, E, _ = _dims(cfg)
    return H * E * weight_bytes + rows * H * act_bytes + rows * E * 4.0


def expert_block_least_seconds(rows: int, cfg: dict, peak: dict,
                               experts_hit: Optional[float] = None):
    """The least time of the grouped matmuls of ONE expert block run on
    ``rows`` valid token rows, and which bound sets it.  ``experts_hit``:
    how many experts were given a row, where the program counted them;
    else the uniform expectation."""
    _, _, _, K = _dims(cfg)
    a = float(rows * K)
    if experts_hit is None:
        experts_hit = expected_experts_hit(rows, cfg)
    return flops.roofline_seconds(
        grouped_matmul_flops(a, cfg),
        grouped_matmul_bytes(a, experts_hit, cfg), peak)
