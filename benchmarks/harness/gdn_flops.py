"""Operations and bytes of a gated-delta layer's CORE — the yardstick's own
arithmetic for the gated delta rule, whatever computes it.

A PREFILL CHUNK is counted in the chunked (WY) form at blocks of
:data:`BLOCK` rows, the form every implementation of a chunk takes (the
token recurrence is sequential in the rows).  A block of ``C`` rows of one
head (``Dk`` key channels, ``Dv`` value channels) needs five ``[C, C]``
products — ``K K^T``, ``T (beta K e^gamma)``, ``Q K^T`` at ``2 C^2 Dk`` each,
``T (beta V)`` and ``(Q K^T) D`` at ``2 C^2 Dv`` — and three products against
the state, ``W S``, ``Q S`` and ``K^T D`` at ``2 C Dk Dv`` each: 11.5 MFLOP at
64 rows of 128 x 128.  The inverse ``T`` of the unit lower-triangular ``[C,
C]`` matrix is NOT counted: what it costs is the implementation's (squarings
or substitution), and the decays, the gates and the norm are elementwise.
Bytes: the float32 state of the one sequence read and written once, its
convolution taps likewise (the activations' dtype), and a row's q, k and v
read and o written.

A DECODE is counted by its bytes: ONE read and one write of every stepped
row's state and taps, and the row's q, k, v and o — the same whatever steps
the rows (its operations, ``6 Dk Dv`` a head, are a thousandth of that time).

The projections, the gated norm and the output projection are matmuls and
elementwise work like any layer's and are not the core.

Keys are the published ``config.json`` names (``linear_num_key_heads``,
``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_conv_kernel_dim``,
``full_attention_interval``, ``num_hidden_layers``), read from the cell's
configuration file.
"""

from __future__ import annotations

from benchmarks.harness import flops

STATE_BYTES = 4    # the state is float32
BLOCK = 64         # rows of a block of the chunked form


def _dims(cfg: dict):
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"])


def delta_layers(cfg: dict) -> int:
    """The gated-delta layers of the configuration: every layer but each
    ``full_attention_interval``-th; 0 for a configuration without them."""
    every = cfg.get("full_attention_interval")
    if not every or "linear_num_value_heads" not in cfg:
        return 0
    n = cfg["num_hidden_layers"]
    return n - n // every


def chunk_flops(rows: float, cfg: dict) -> float:
    """The matmuls of the chunked form over ``rows`` token rows of one
    layer: whole blocks of :data:`BLOCK` rows (a ragged last block is
    computed whole)."""
    _, hv, dk, dv, _ = _dims(cfg)
    blocks = -(-rows // BLOCK)
    c = float(BLOCK)
    return blocks * hv * (3 * 2 * c * c * dk + 2 * 2 * c * c * dv
                          + 3 * 2 * c * dk * dv)


def core_bytes(rows: float, sequences: float, cfg: dict,
               act_bytes: int = 2) -> float:
    """Least HBM traffic of one layer's core on ``rows`` token rows of
    ``sequences`` sequences: each sequence's state and taps read and written
    once, each row's q, k, v read and o written."""
    hk, hv, dk, dv, k = _dims(cfg)
    channels = 2 * hk * dk + hv * dv
    return (sequences * 2.0 * (hv * dk * dv * STATE_BYTES
                               + (k - 1) * channels * act_bytes)
            + rows * (channels + hv * dv) * act_bytes)


def chunk_least_seconds(rows: float, cfg: dict, peak: dict):
    """The least time of ONE layer's core on a prefill chunk of ``rows``
    token rows of one sequence, and which bound sets it."""
    return flops.roofline_seconds(chunk_flops(rows, cfg),
                                  core_bytes(rows, 1.0, cfg), peak)


def step_least_seconds(rows: float, cfg: dict, peak: dict) -> float:
    """The least time of ONE layer's core on a decode that steps ``rows``
    state rows: their bytes over the HBM's bandwidth."""
    return core_bytes(rows, rows, cfg) / peak["hbm_bytes_per_s"]
