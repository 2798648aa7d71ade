"""Pipeline-parallel training CLI over the SPMD pipeline.

The training counterpart of tools/generate.py (beyond-reference: the
upstream framework is inference-only). Builds the one-program pipelined
forward over a ('dp', 'stage') mesh, differentiates through it
(parallel/train.py), and runs an optimizer loop on synthetic data —
classification (ViT/DeiT: images + labels), BERT sequence
classification (token ids + class labels), or causal-LM (GPT-2/LLaMA/
Mistral families: next-token targets). Checkpoints the full training
state (params + optimizer + step) via Orbax and resumes from it.

Examples:
  python tools/train.py -m pipeedge/test-tiny-vit --steps 20 --platform cpu
  python tools/train.py -m gpt2 -pt 1,24,25,48 --dp 2 --steps 100 \\
      --optimizer adam --ckpt-dir /tmp/gpt2_train --remat
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("-m", "--model-name", default="pipeedge/test-tiny-vit")
    p.add_argument("-pt", "--partition", default=None,
                   help="comma-separated block-aligned layer bounds "
                        "(default: one stage)")
    p.add_argument("--dp", default=1, type=int,
                   help="data-parallel mesh axis (batch shards)")
    p.add_argument("--steps", default=10, type=int)
    p.add_argument("-b", "--batch", default=4, type=int)
    p.add_argument("-u", "--ubatches", default=4, type=int,
                   help="microbatches per step (the pipeline's fill depth)")
    p.add_argument("--seq-len", default=32, type=int,
                   help="sequence length for LM families")
    p.add_argument("--lr", default=1e-3, type=float)
    p.add_argument("--optimizer", default="sgd", choices=["sgd", "adam"])
    p.add_argument("-t", "--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--remat", action="store_true",
                   help="per-block jax.checkpoint (trades a forward "
                        "recompute for ~model-depth less activation HBM)")
    p.add_argument("--mixed-precision", action="store_true",
                   help="bf16 compute over f32 master weights (requires "
                        "-t float32: those params ARE the masters)")
    p.add_argument("--ckpt-dir", default=None,
                   help="save the training state here every --ckpt-every "
                        "steps and resume from it when present")
    p.add_argument("--ckpt-every", default=0, type=int,
                   help="0 = only at the end")
    p.add_argument("--log-every", default=1, type=int)
    p.add_argument("--seed", default=0, type=int)
    p.add_argument("--platform", default=None,
                   help="force a jax platform (e.g. cpu)")
    args = p.parse_args()

    if args.mixed_precision and args.dtype != "float32":
        p.error("--mixed-precision keeps f32 master weights; use -t "
                "float32 (the bf16 cast is per-step, inside the program)")
    from pipeedge_tpu.utils import (enable_compile_cache,
                                    report_device_memory, report_devices)
    enable_compile_cache()
    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    report_devices()
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pipeedge_tpu.models import ShardConfig, registry
    from pipeedge_tpu.parallel import spmd, train

    cfg = registry.get_model_config(args.model_name)
    total = registry.get_model_layers(args.model_name)
    if args.partition:
        nums = [int(x) for x in args.partition.split(",")]
        if len(nums) % 2:
            p.error(f"-pt needs an even count of layer bounds: {nums}")
        partition = list(zip(nums[::2], nums[1::2]))
        from pipeedge_tpu.parallel.decode import validate_partition
        try:
            validate_partition(partition, total)
        except ValueError as exc:
            p.error(f"-pt: {exc} ({args.model_name} has {total} sublayers)")
    else:
        partition = [(1, total)]
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    entry = registry.get_model_entry(args.model_name)
    family_mod = entry.family
    is_lm = cfg.model_type in ("gpt2", "llama")
    is_bert = cfg.model_type == "bert"
    if not is_lm and not is_bert and cfg.model_type not in ("vit", "deit"):
        p.error(f"training CLI covers classification (vit/deit), BERT "
                f"sequence classification, and LM (gpt2/llama) families; "
                f"got {cfg.model_type}")

    stage_params = [family_mod.init_params(
        cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == total),
        dtype=dtype, seed=args.seed) for l, r in partition]
    n_stages = len(partition)
    need = n_stages * args.dp
    if len(jax.devices()) < need:
        p.error(f"{n_stages} stages x dp {args.dp} needs {need} devices, "
                f"have {len(jax.devices())}")
    mesh = spmd.make_pipeline_mesh(n_stages, dp=args.dp)
    pipe = spmd.build_spmd_pipeline(family_mod.FAMILY, cfg, partition,
                                    stage_params, mesh, remat=args.remat)

    rng = np.random.default_rng(args.seed)
    if is_lm:
        seq = min(args.seq_len + 1, cfg.max_position_embeddings)
        ids = jnp.asarray(rng.integers(
            0, cfg.vocab_size, size=(args.ubatches, args.batch, seq)),
            jnp.int32)
        inputs, labels = ids[..., :-1], ids[..., 1:]
    elif is_bert:
        seq = min(args.seq_len, cfg.max_position_embeddings)
        inputs = jnp.asarray(rng.integers(
            0, cfg.vocab_size, size=(args.ubatches, args.batch, seq)),
            jnp.int32)
        labels = jnp.asarray(rng.integers(
            0, max(cfg.num_labels, 1), size=(args.ubatches, args.batch)),
            jnp.int32)
    else:
        inputs = jnp.asarray(rng.normal(size=(
            args.ubatches, args.batch, 3, cfg.image_size, cfg.image_size)),
            dtype)
        labels = jnp.asarray(rng.integers(
            0, max(cfg.num_labels, 1), size=(args.ubatches, args.batch)),
            jnp.int32)

    opt = (optax.adam(args.lr) if args.optimizer == "adam"
           else optax.sgd(args.lr))
    step_fn, opt_state = train.make_train_step(
        pipe, opt, inputs, mixed_precision=args.mixed_precision)
    params, start = pipe.params, 0
    if args.ckpt_dir and os.path.isdir(args.ckpt_dir) \
            and os.listdir(args.ckpt_dir):   # a real checkpoint, not just
        params, opt_state, start = train.restore_train_state(  # a mkdir
            args.ckpt_dir, params, opt_state)
        print(f"resumed from {args.ckpt_dir} at step {start}", flush=True)

    tik = time.monotonic()
    loss = None
    for i in range(start, args.steps):
        params, opt_state, loss = step_fn(params, opt_state, inputs, labels)
        if args.log_every and (i + 1) % args.log_every == 0:
            print(f"step={i + 1} loss={float(loss):.4f}", flush=True)
        if args.ckpt_dir and args.ckpt_every \
                and (i + 1) % args.ckpt_every == 0:
            train.save_train_state(args.ckpt_dir, params, opt_state, i + 1)
    wall = time.monotonic() - tik
    report_device_memory()
    done = max(args.steps - start, 0)
    if args.ckpt_dir and done:
        # never write a checkpoint whose step count moves BACKWARD (a
        # --steps below the restored step trains nothing and must not
        # relabel step-`start` state as something earlier)
        train.save_train_state(args.ckpt_dir, params, opt_state,
                               start + done)
    print(json.dumps({
        "steps": done,
        "final_loss": round(float(loss), 4) if loss is not None else None,
        "images_or_seqs_per_step": args.ubatches * args.batch,
        "wall_s": round(wall, 2),
        "steps_per_sec": round(done / wall, 3) if wall > 0 and done else None,
        "mesh": dict(mesh.shape), "remat": args.remat,
        "mixed_precision": args.mixed_precision,
        "ckpt": args.ckpt_dir}), flush=True)


if __name__ == "__main__":
    main()
