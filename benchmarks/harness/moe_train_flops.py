"""Operations and bytes of the grouped matmuls of a TRAINED routed expert
block that holds a share of its experts, SwiGLU experts — the yardstick's
own arithmetic for ``moe_held_gated_train_roofline``
(``moe_held_gated_flops.py`` beside it counts the forward alone, as a served
block runs it).

An ASSIGNMENT is one (token, expert) pair, HELD where its expert's weights
are here.  A held assignment costs NINE matmuls of ``2 x H x I``: the gate,
the up and the down projection, each forward, each data gradient (the
cotangent times the weight, flipped), each weight gradient (the rows,
transposed, times the cotangent).  A forward recomputed under remat is the
program's choice and does not count.  Bytes: the three weights of the held
experts HIT are read twice (forward, data gradient) and their gradients
written once, in the compute dtype; every one of the nine reads and writes
its row operands once.

Keys are the published ``config.json`` names, read from the cell's
configuration file.
"""

from __future__ import annotations

from benchmarks.harness import flops

MATMULS = 9     # (gate, up, down) x (forward, data gradient, weight gradient)


def grouped_matmul_flops(assignments_held: float, cfg: dict) -> float:
    return MATMULS * assignments_held * 2.0 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"]


def grouped_matmul_bytes(assignments_held: float, experts_hit: float,
                         cfg: dict, weight_bytes: int = 2,
                         act_bytes: int = 2) -> float:
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (experts_hit * 3.0 * H * F * 3 * weight_bytes
            + assignments_held * MATMULS * (H + F) * act_bytes)


def expert_block_least_seconds(assignments_held: float, experts_hit: float,
                               cfg: dict, peak: dict):
    """The least time of the nine grouped matmuls of ONE expert block of one
    training step, and which bound sets it."""
    return flops.roofline_seconds(
        grouped_matmul_flops(assignments_held, cfg),
        grouped_matmul_bytes(assignments_held, experts_hit, cfg), peak)
