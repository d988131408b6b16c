"""HF ↔ framework checkpoint conversion CLI.

Script-level counterpart of the reference's
``examples/training/llama2/convert_checkpoints.py`` (HF↔NxD state-dict
conversion), built on :mod:`neuronx_distributed_tpu.convert`:

    # HF -> framework (orbax dir consumable by trainer.load_checkpoint)
    python examples/convert_checkpoints.py to-framework \
        --family llama --hf /path/to/hf_model_dir --out /tmp/fw_ckpt \
        --config llama2_7b

    # framework -> HF (safetensors)
    python examples/convert_checkpoints.py to-hf \
        --family llama --ckpt /tmp/fw_ckpt --out /tmp/hf_out --config llama2_7b

HF side accepts a directory containing ``*.safetensors`` (preferred) or
``pytorch_model*.bin`` shards.  The framework side is the same orbax layout
``trainer.checkpoint`` reads ("model" payload of a tag dir).
"""

import argparse
import glob
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _load_hf_state_dict(path):
    sd = {}
    st_files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
    if st_files:
        from safetensors import safe_open

        for f in st_files:
            with safe_open(f, framework="np") as fh:
                for k in fh.keys():
                    sd[k] = fh.get_tensor(k)
        return sd
    bin_files = sorted(glob.glob(os.path.join(path, "pytorch_model*.bin"))) or sorted(
        glob.glob(os.path.join(path, "*.pt"))
    )
    if not bin_files:
        raise FileNotFoundError(f"no *.safetensors or pytorch_model*.bin under {path}")
    import torch

    for f in bin_files:
        blob = torch.load(f, map_location="cpu", weights_only=True)
        for k, v in blob.items():
            sd[k] = v.numpy() if hasattr(v, "numpy") else np.asarray(v)
    return sd


def _save_hf_state_dict(sd, path):
    os.makedirs(path, exist_ok=True)
    try:
        from safetensors.numpy import save_file

        save_file({k: np.ascontiguousarray(v) for k, v in sd.items()},
                  os.path.join(path, "model.safetensors"))
    except ImportError:  # pragma: no cover - safetensors ships with transformers
        import torch

        torch.save({k: torch.from_numpy(np.ascontiguousarray(v)) for k, v in sd.items()},
                   os.path.join(path, "pytorch_model.bin"))


def _family(args):
    # conversion is pure host-side layout algebra: never touch an accelerator
    # backend (on a chip machine the default platform is the TPU, and taking
    # it here would hold the chip from the process that needs it)
    import jax

    jax.config.update("jax_platforms", args.platform)
    from neuronx_distributed_tpu import convert as C

    def build_cfg(cls):
        if not args.config:
            return cls()
        if args.config.endswith(".json") or os.path.exists(args.config):
            with open(args.config) as f:
                return cls(**json.load(f))
        return getattr(cls, args.config)()

    if args.family == "llama":
        from neuronx_distributed_tpu.models.llama import LlamaConfig

        return build_cfg(LlamaConfig), C.llama_params_from_hf, C.llama_params_to_hf
    if args.family == "gpt_neox":
        from neuronx_distributed_tpu.models.gpt_neox import GPTNeoXConfig

        return build_cfg(GPTNeoXConfig), C.gpt_neox_params_from_hf, C.gpt_neox_params_to_hf
    if args.family == "bert":
        from neuronx_distributed_tpu.models.bert import BertConfig

        return build_cfg(BertConfig), C.bert_params_from_hf, C.bert_params_to_hf
    if args.family == "gemma":
        from neuronx_distributed_tpu.models.gemma import GemmaConfig

        return build_cfg(GemmaConfig), C.gemma_params_from_hf, C.gemma_params_to_hf
    if args.family == "gemma2":
        from neuronx_distributed_tpu.models.gemma import Gemma2Config

        return build_cfg(Gemma2Config), C.gemma2_params_from_hf, C.gemma2_params_to_hf
    raise ValueError(f"unknown family {args.family}")


def cmd_to_framework(args):
    import orbax.checkpoint as ocp

    cfg, from_hf, _ = _family(args)
    sd = _load_hf_state_dict(args.hf)
    params = from_hf(sd, cfg)
    ocp.Checkpointer(ocp.StandardCheckpointHandler()).save(
        os.path.join(os.path.abspath(args.out), "model"),
        args=ocp.args.StandardSave(params), force=True,
    )
    n = sum(int(np.asarray(x).size) for x in _leaves(params))
    with open(os.path.join(args.out, "meta.json"), "w") as f:
        json.dump({"tag": "hf_import", "family": args.family, "config": args.config}, f)
    print(json.dumps({"params": n, "out": args.out}))


def cmd_to_hf(args):
    import orbax.checkpoint as ocp

    cfg, _, to_hf = _family(args)
    params = ocp.Checkpointer(ocp.StandardCheckpointHandler()).restore(
        os.path.join(os.path.abspath(args.ckpt), "model")
    )
    if "layers" in params and "head" in params:
        # pipeline-engine checkpoint ({embed, layers: stacked, head}): flatten
        # through layer_rows (uneven cuts / padding) to the standard tree
        import neuronx_distributed_tpu.convert as C

        stack_rows = next(iter(_leaves(params["layers"]))).shape[0]
        if args.layer_rows is None:
            if stack_rows != cfg.num_layers:
                raise SystemExit(
                    f"pipelined stack has {stack_rows} rows but the config has "
                    f"{cfg.num_layers} layers (uneven pipeline_cuts / padding): "
                    "pass --layer-rows with the PipelinedModel.layer_rows "
                    "mapping — an identity default would export padding rows "
                    "as layers")
            rows = list(range(cfg.num_layers))
        else:
            rows = [int(r) for r in args.layer_rows.split(",")]
            if len(rows) != cfg.num_layers or (rows and max(rows) >= stack_rows):
                raise SystemExit(
                    f"--layer-rows must list {cfg.num_layers} rows < {stack_rows}")
        flat = {
            "llama": C.llama_params_from_pipelined,
            "gpt_neox": C.gpt_neox_params_from_pipelined,
        }.get(args.family)
        if flat is None:
            raise SystemExit(f"pipelined checkpoints unsupported for {args.family}")
        params = flat(params, rows)
    sd = to_hf(params, cfg)
    _save_hf_state_dict(sd, args.out)
    print(json.dumps({"tensors": len(sd), "out": args.out}))


def _leaves(tree):
    if isinstance(tree, dict):
        for v in tree.values():
            yield from _leaves(v)
    else:
        yield tree


def main():
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)
    for name, fn in (("to-framework", cmd_to_framework), ("to-hf", cmd_to_hf)):
        sp = sub.add_parser(name)
        sp.add_argument("--family", required=True, choices=["llama", "gpt_neox", "bert", "gemma", "gemma2"])
        sp.add_argument("--config", default=None,
                        # a preset name (tiny, llama2_7b, ...) or a JSON file
                        # of config-field overrides
                        help="preset name on the family config (e.g. llama2_7b, tiny)")
        sp.add_argument("--platform", default="cpu",
                        help="jax platform for the conversion (default cpu)")
        sp.add_argument("--out", required=True)
        if name == "to-framework":
            sp.add_argument("--hf", required=True, help="HF model directory")
        else:
            sp.add_argument("--ckpt", required=True, help="framework checkpoint tag dir")
            sp.add_argument("--layer-rows", default=None,
                            help="comma-separated stack row of each real layer for "
                                 "pipeline-engine checkpoints with uneven cuts / "
                                 "padding (default: identity 0..num_layers-1)")
        sp.set_defaults(fn=fn)
    args = p.parse_args()
    sys.exit(args.fn(args))


if __name__ == "__main__":
    main()
