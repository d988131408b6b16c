#!/usr/bin/env bash
# Multi-host launch on a TPU pod slice — the framework-native analogue of
# the reference's torchrun/SLURM launch scripts
# (reference examples/training/llama2/tp_zero1_llama2_7b_hf_pretrain/
#  tp_zero1_llama2_7b_hf_pretrain.sh:44-56).
#
# On Cloud TPU VMs, run the SAME command on every host of the slice (e.g.
# via `gcloud compute tpus tpu-vm ssh $NAME --worker=all --command=...`).
# jax.distributed picks the coordinator and process ids up from the TPU
# metadata automatically, so no torchrun-style rendezvous flags are needed;
# utils.initialize_distributed() (called by every launcher) is a no-op on
# one host and brings the pod up on many.
#
# The mesh spans all hosts: 32 chips (v5e-32) below give TP=8 within hosts
# and DP=4 across them — the north-star topology (Llama-2-7B pretrain).
# Shardings ride ICI within a host-block and DCN across; the mesh device
# order (parallel/mesh.py multi-slice layout) keeps tp/cp/kvr axes on ICI.
set -euo pipefail

REPO="$(cd "$(dirname "$0")/../../.." && pwd)"
cd "$REPO"

: "${PRESET:=llama2_7b}"
: "${TP:=8}"
: "${BATCH:=256}"          # global batch, split over dp automatically
: "${SEQ:=4096}"
: "${STEPS:=1000}"
: "${DATA:=}"              # NXDT token file (synthetic when empty)
: "${CKPT_DIR:=}"

ARGS=(
  --preset "$PRESET" --tp "$TP"
  --batch-size "$BATCH" --seq-len "$SEQ" --steps "$STEPS"
  --attention flash --loss-chunk 512
)
[[ -n "$DATA" ]] && ARGS+=(--data "$DATA")
[[ -n "$CKPT_DIR" ]] && ARGS+=(--ckpt-dir "$CKPT_DIR" --ckpt-every 100 --resume)

exec python examples/training/llama_pretrain.py "${ARGS[@]}"
