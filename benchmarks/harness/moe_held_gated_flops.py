"""Operations and bytes of a routed expert block that HOLDS a share of its
experts and whose experts ARE gated — the yardstick's own arithmetic for the
grouped matmuls of ``down(silu(gate x) * up x)`` experts on one
expert-parallel rank (``moe_held_flops.py`` beside it counts the two matmuls
of an ungated held expert, ``moe_flops.py`` three matmuls an assignment over
every expert of a mixture whole on the chip).

An ASSIGNMENT is one (token, expert) pair; it is HELD where its expert's
weights are here.  Operations count the held assignments, three matmuls each
(what this rank's part of the mathematics requires); bytes count the gate,
up and down weights of the held experts that were HIT — an expert no row
chose is never read — plus the rows in and out of each matmul.

Keys are the published ``config.json`` names (``hidden_size``,
``moe_intermediate_size`` = the width of one routed expert), read from the
cell's configuration file.
"""

from __future__ import annotations

from benchmarks.harness import flops


def grouped_matmul_flops(assignments_held: float, cfg: dict) -> float:
    """Gate, up and down of every held assignment: 3 x 2 x H x F each."""
    return 3.0 * assignments_held * 2 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"]


def grouped_matmul_bytes(assignments_held: float, experts_hit: float,
                         cfg: dict, weight_bytes: int = 2,
                         act_bytes: int = 2) -> float:
    """Least HBM traffic of the three grouped matmuls of one expert block:
    the gate, up and down weights of the held experts hit, once; the gate
    and the up each read ``[A, H]`` and write ``[A, F]``, the down reads
    ``[A, F]`` and writes ``[A, H]``."""
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (experts_hit * 3.0 * H * F * weight_bytes
            + assignments_held * 3.0 * (H + F) * act_bytes)


def expert_block_least_seconds(assignments_held: float, experts_hit: float,
                               cfg: dict, peak: dict):
    """The least time of the grouped matmuls of ONE expert block given
    ``assignments_held`` rows over ``experts_hit`` held experts, and which
    bound sets it."""
    return flops.roofline_seconds(
        grouped_matmul_flops(assignments_held, cfg),
        grouped_matmul_bytes(assignments_held, experts_hit, cfg), peak)
