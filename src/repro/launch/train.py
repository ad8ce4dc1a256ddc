"""Production training launcher: mesh + sharded state + checkpoint/restart.

On real TPU pods this is the per-host entrypoint (jax.distributed.initialize
is called when JAX_COORDINATOR is set); on CPU it runs reduced configs for
end-to-end validation. The fault-tolerance supervisor wraps the step loop:
on HostFailure it restores the latest checkpoint (resharded if the mesh
shrank) and continues.

Usage (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --smoke \
      --steps 20 --batch 4 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import os
import time

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--data", type=int, default=1, help="data-axis size")
    ap.add_argument("--model", type=int, default=1, help="model-axis size")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--sketch-grads", type=int, default=0,
                    help="r' for SRHT gradient compression (0 = off)")
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    if "JAX_COORDINATOR" in os.environ:      # multi-host entry
        jax.distributed.initialize()

    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models.registry import get_api
    from repro.train import steps as tsteps
    from repro.train.optimizer import AdamWConfig
    from repro.distributed import sharding as shd
    from repro.distributed.checkpoint import CheckpointManager
    from repro.launch import specs
    from repro.launch.mesh import make_debug_mesh, dp_axes

    cfg = get_config(args.arch, smoke=args.smoke)
    api = get_api(cfg)
    mesh = make_debug_mesh(args.data, args.model)
    tp = args.model
    key = jax.random.PRNGKey(0)
    state = tsteps.init_train_state(key, cfg, api, tp=tp)
    state_spec = shd.state_pspecs(jax.eval_shape(
        lambda: tsteps.init_train_state(key, cfg, api, tp=tp)), mesh)
    def ns(spec):
        return jax.tree.map(lambda p: NamedSharding(mesh, p), spec,
                            is_leaf=lambda q: isinstance(q, P))
    state = jax.device_put(state, ns(state_spec))

    grad_transform = None
    ef_holder = {}
    if args.sketch_grads:
        from repro.distributed.compression import make_sketched_grad_transform
        transform, init_ef = make_sketched_grad_transform(
            state.params, r_prime=args.sketch_grads)
        ef_holder["ef"] = init_ef()
        ef_holder["t"] = 0

        def grad_transform(grads):
            g, ef_holder["ef"] = transform(
                grads, ef_holder["ef"],
                jax.random.PRNGKey(ef_holder["t"]))
            ef_holder["t"] += 1
            return g

    opt_cfg = AdamWConfig(lr=args.lr, moment_dtype=cfg.optimizer_dtype)
    # A fixed synthetic corpus: the model must drive loss down on it.
    batch = specs.train_inputs(cfg, args.seq, args.batch, concrete=True,
                               key=jax.random.PRNGKey(7))
    batch_spec = shd.batch_pspecs(jax.eval_shape(lambda: batch), mesh)
    batch = jax.device_put(batch, ns(batch_spec))

    mgr = (CheckpointManager(args.ckpt_dir, save_every=args.ckpt_every)
           if args.ckpt_dir else None)
    start = 0
    if mgr is not None:
        try:
            state, start = mgr.restore_latest(jax.eval_shape(lambda: state))
            print(f"restored checkpoint at step {start}")
        except FileNotFoundError:
            pass

    with mesh:
        with shd.activation_sharding(dp_axes(mesh)):
            step_jit = jax.jit(
                tsteps.make_train_step(cfg, api, groups=args.data,
                                       grad_transform=None,
                                       opt_cfg=opt_cfg),
                in_shardings=(ns(state_spec), ns(batch_spec)),
                out_shardings=(ns(state_spec), None),
                donate_argnums=(0,))
            losses = []
            t0 = time.time()
            for step in range(start, args.steps):
                if grad_transform is not None:
                    # Eager path when compressing (EF state lives outside
                    # jit; production uses the shard_map variant).
                    sfn = tsteps.make_train_step(
                        cfg, api, groups=args.data,
                        grad_transform=grad_transform, opt_cfg=opt_cfg)
                    state, metrics = sfn(state, batch)
                else:
                    state, metrics = step_jit(state, batch)
                losses.append(float(metrics["loss"]))
                if mgr is not None:
                    mgr.maybe_save(step + 1, state)
                if step % 10 == 0 or step == args.steps - 1:
                    print(f"step {step:5d} loss {losses[-1]:.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"({(time.time()-t0):.1f}s)", flush=True)
            print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")
            assert losses[-1] < losses[0], "loss did not decrease"


if __name__ == "__main__":
    main()
