"""Operations and bytes of a Mamba-2 layer's CORE — the yardstick's own
arithmetic for the selective scan, whatever chunked form computes it.

What the recurrence requires of one token and head (``P`` channels, state
size ``N``) is the state update ``(dt x) (x) B`` and the read ``S C`` — ``2 P
N`` each (the decay is a multiply by a scalar a head and is not counted, nor
is the ``D`` skip).  Bytes: the float32 state of every sequence a call
continues is read and written once, and so are its convolution taps (the
activations' dtype); x, B and C are read and y written, a row each.  The
projections, the gate and the norm are matmuls and elementwise work like any
layer's and are not the core.

Keys are the published ``config.json`` names (``mamba_num_heads``,
``mamba_head_dim``, ``n_groups``, ``ssm_state_size``, ``conv_kernel``), read
from the cell's configuration file.
"""

from __future__ import annotations

from benchmarks.harness import flops

STATE_BYTES = 4    # the scan state is float32


def _dims(cfg: dict):
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"], cfg["conv_kernel"])


def scan_flops(rows: float, cfg: dict) -> float:
    nh, p, _, n, _ = _dims(cfg)
    return rows * nh * 4.0 * p * n


def scan_bytes(rows: float, sequences: float, cfg: dict,
               act_bytes: int = 2) -> float:
    nh, p, g, n, k = _dims(cfg)
    channels = nh * p + 2 * g * n
    return (sequences * 2.0 * (nh * p * n * STATE_BYTES
                               + (k - 1) * channels * act_bytes)
            + rows * (channels + nh * p) * act_bytes)


def scan_least_seconds(rows: float, sequences: float, cfg: dict, peak: dict):
    """The least time of ONE Mamba-2 layer's core on ``rows`` token rows of
    ``sequences`` sequences, and which bound sets it."""
    return flops.roofline_seconds(scan_flops(rows, cfg),
                                  scan_bytes(rows, sequences, cfg), peak)
