"""Production training launcher: builds the mesh, attaches sharding rules,
and runs the fault-tolerant training loop with sharded params/opt-state.

On this container it runs reduced configs on small host-device meshes
(``--devices N`` sets --xla_force_host_platform_device_count); on a real
TPU cluster the same entrypoint runs under the runtime's process-per-host
launcher with the production mesh.

    PYTHONPATH=src python -m repro.launch.train --arch llama3_8b --reduced \
        --devices 8 --mesh 2x4 --steps 20
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_8b")
    ap.add_argument("--reduced", action="store_true", help="CPU-scale config")
    ap.add_argument("--devices", type=int, default=0, help="force host device count")
    ap.add_argument("--mesh", default="", help="e.g. 2x4 → (data=2, model=4); 2x2x2 adds pod")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--optimizer", choices=["adamw", "ebv"], default="adamw")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    import jax
    from repro.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    from repro.configs.base import get_config
    from repro.dist import sharding as shlib
    from repro.launch.mesh import make_production_mesh, parse_mesh_arg
    from repro.launch import specs as S
    from repro.models import lm
    from repro.train.loop import TrainConfig, train

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.replace(**{k: v for k, v in vars(cfg.reduced()).items() if k != "name"})

    mesh = None
    if args.mesh:
        mesh = parse_mesh_arg(args.mesh)
    elif jax.device_count() >= 256:
        mesh = make_production_mesh(multi_pod=jax.device_count() >= 512)

    tc = TrainConfig(
        steps=args.steps, seq_len=args.seq_len, global_batch=args.batch,
        microbatches=args.microbatches, learning_rate=args.lr,
        warmup_steps=max(args.steps // 10, 2), optimizer=args.optimizer,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
    )

    if mesh is None:
        train(cfg, tc)
        return

    with shlib.use_mesh_rules(mesh):
        p_axes = lm.param_axes(cfg)
        p_struct = jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.PRNGKey(tc.seed))
        p_sh = S.shardings_for_args(p_struct, p_axes, mesh)
        params = jax.jit(
            lambda k: lm.init_params(k, cfg), out_shardings=p_sh
        )(jax.random.PRNGKey(tc.seed))
        print(f"[launch] mesh={dict(mesh.shape)} params sharded across {mesh.devices.size} devices")
        train(cfg, tc, params=params)


if __name__ == "__main__":
    main()
