"""Multi-device distributed checks, run in a subprocess with
xla_force_host_platform_device_count=8 (see test_distributed.py).

Exit code 0 = all assertions passed.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P


def _mesh(shape, names):
    """A mesh in Auto sharding mode, the mode the library is written
    for (jax.make_mesh defaults to Explicit axes)."""
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names))


def check_distributed_fwht():
    from repro.distributed.dfwht import distributed_fwht
    from repro.core.sketch import fwht

    mesh = _mesh((8,), ("data",))
    for n, c in [(64, 4), (512, 3), (8, 1)]:
        x = jax.random.normal(jax.random.PRNGKey(n), (n, c))
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        got = distributed_fwht(xs, mesh, "data")
        want = fwht(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
    print("distributed_fwht ok")


def check_dfwht_on_2d_mesh():
    from repro.distributed.dfwht import distributed_fwht
    from repro.core.sketch import fwht

    mesh = _mesh((4, 2), ("data", "model"))
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 2))
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    got = distributed_fwht(xs, mesh, "data")
    np.testing.assert_allclose(np.asarray(got), np.asarray(fwht(x)),
                               rtol=2e-4, atol=2e-4)
    print("dfwht 2d-mesh ok")


def check_sharded_train_step():
    """End-to-end: mixtral smoke config trains under a (2, 2) mesh with the
    production sharding rules; loss finite, params update."""
    from repro.configs import get_config
    from repro.models.registry import get_api
    from repro.train import steps as tsteps
    from repro.distributed import sharding as shd
    from repro.launch import specs
    from repro.launch.mesh import dp_axes

    mesh = _mesh((2, 2), ("data", "model"))
    cfg = get_config("mixtral-8x7b", smoke=True)
    api = get_api(cfg)
    state = tsteps.init_train_state(jax.random.PRNGKey(0), cfg, api, tp=2)
    state_spec = shd.state_pspecs(
        jax.eval_shape(lambda: tsteps.init_train_state(
            jax.random.PRNGKey(0), cfg, api, tp=2)), mesh)
    batch = specs.train_inputs(cfg, 32, 4, concrete=True,
                               key=jax.random.PRNGKey(1))
    batch_spec = shd.batch_pspecs(jax.eval_shape(lambda: batch), mesh)

    def ns(spec):
        return jax.tree.map(lambda p: NamedSharding(mesh, p), spec,
                            is_leaf=lambda q: isinstance(q, P))
    state = jax.device_put(state, ns(state_spec))
    batch = jax.device_put(batch, ns(batch_spec))
    with mesh:
        with shd.activation_sharding(dp_axes(mesh)):
            step = jax.jit(tsteps.make_train_step(cfg, api, groups=2),
                           in_shardings=(ns(state_spec), ns(batch_spec)),
                           out_shardings=(ns(state_spec), None))
            state2, m1 = step(state, batch)
            state3, m2 = step(state2, batch)
    assert np.isfinite(float(m1["loss"]))
    assert float(m2["loss"]) < float(m1["loss"])
    print("sharded_train_step ok", float(m1["loss"]), "->",
          float(m2["loss"]))


def check_sharded_vs_single_device_loss():
    """Same batch, same params: sharded loss == unsharded loss."""
    from repro.configs import get_config
    from repro.models.registry import get_api
    from repro.train import steps as tsteps
    from repro.launch import specs

    cfg = get_config("qwen3-14b", smoke=True)
    api = get_api(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg, tp=1)
    batch = specs.train_inputs(cfg, 32, 4, concrete=True,
                               key=jax.random.PRNGKey(1))
    logits_1dev = api.forward(params, cfg, batch, 1)
    loss_1dev = float(tsteps.cross_entropy(logits_1dev, batch["labels"]))

    from repro.distributed import sharding as shd
    mesh = _mesh((4, 2), ("data", "model"))
    ps = shd.param_pspecs(jax.eval_shape(lambda: params), mesh)
    bs = shd.batch_pspecs(jax.eval_shape(lambda: batch), mesh)

    def ns(spec):
        return jax.tree.map(lambda p: NamedSharding(mesh, p), spec,
                            is_leaf=lambda q: isinstance(q, P))
    params_s = jax.device_put(params, ns(ps))
    batch_s = jax.device_put(batch, ns(bs))
    with mesh:
        logits_s = jax.jit(lambda p, b: api.forward(p, cfg, b, 1),
                           in_shardings=(ns(ps), ns(bs)))(params_s, batch_s)
    loss_s = float(tsteps.cross_entropy(logits_s, batch["labels"]))
    assert abs(loss_s - loss_1dev) < 1e-2 * max(1.0, abs(loss_1dev)), (
        loss_s, loss_1dev)
    print("sharded_vs_single ok", loss_1dev, loss_s)


def check_sketched_allreduce_pmean():
    """Sketch all-reduce inside shard_map: mean of per-shard gradients
    (projected) equals projection of the mean."""
    from jax import shard_map
    from repro.distributed.compression import (sketch_params, compress,
                                               decompress)
    mesh = _mesh((8,), ("data",))
    n = 256
    g = jax.random.normal(jax.random.PRNGKey(0), (8, n))
    signs, rows = sketch_params(jax.random.PRNGKey(1), n, 32)

    def body(gl):
        s = compress(gl[0], signs, rows)
        s = jax.lax.pmean(s, "data")
        return decompress(s, signs, rows, n)[None]

    out = shard_map(body, mesh=mesh, in_specs=P("data", None),
                    out_specs=P("data", None), check_vma=False)(g)
    want = decompress(compress(jnp.mean(g, 0), signs, rows), signs, rows, n)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=1e-3, atol=1e-4)
    print("sketched_allreduce ok")


def check_sharded_extend():
    """Serving-side sharded extension (serve.extend.ShardedExtender)
    matches the single-device path to fp32 tolerance, end to end through
    MicroBatcher(mesh=) and AsyncBatcher, on ragged n (250 pads to 256
    over 8 shards) — on BOTH stripe engines: the two-pass gram+projection
    body and the fused extend_embed Pallas kernel (interpret mode) run
    per device inside the shard_map."""
    from repro.api import KernelKMeans
    from repro.data import blob_ring
    from repro.serve import (AsyncBatcher, MicroBatcher, ShardedExtender,
                             assign, embed)

    mesh = _mesh((8,), ("data",))
    X, _ = blob_ring(jax.random.PRNGKey(0), n=250)
    Xq = jax.random.normal(jax.random.PRNGKey(2), (2, 101)) * 1.5
    # rbf included: kappa(0, x) != 0, so this exercises the zero-column
    # projection-padding argument, not just harmless zero kernel columns.
    for kernel, params, r in (("polynomial", {"gamma": 0.0, "degree": 2}, 2),
                              ("rbf", {"gamma": 1.0}, 4)):
        m = KernelKMeans(k=2, r=r, kernel=kernel, kernel_params=params,
                         backend_params={"oversampling": 10},
                         block=64).fit(X, key=jax.random.PRNGKey(1)).model_
        ext = ShardedExtender(m, mesh)
        Ys, Y1 = ext.embed(Xq), embed(m, Xq)
        rel = (float(jnp.linalg.norm(Ys - Y1)) /
               max(float(jnp.linalg.norm(Y1)), 1e-30))
        assert rel <= 1e-5, (kernel, rel)
        # fused extend_embed Pallas stripe per device on the 8-way mesh.
        ext_f = ShardedExtender(m, mesh, fused=True, interpret=True)
        rel_f = (float(jnp.linalg.norm(ext_f.embed(Xq) - Y1)) /
                 max(float(jnp.linalg.norm(Y1)), 1e-30))
        assert rel_f <= 1e-5, (kernel, rel_f)
        lab1, _ = assign(m, Xq)
        labs, _ = ext.assign(Xq)
        assert np.array_equal(np.asarray(lab1), np.asarray(labs)), kernel
        lab_f, _ = ext_f.assign(Xq)
        assert np.array_equal(np.asarray(lab1), np.asarray(lab_f)), kernel
        # whole serving stack on the sharded path: bucketed sync + async,
        # two-pass and forced-fused.
        mb = MicroBatcher(m, max_bucket=64, mesh=mesh)
        lab_b, _ = mb.assign_batch(Xq)
        assert np.array_equal(lab_b, np.asarray(lab1)), kernel
        mb_f = MicroBatcher(m, max_bucket=64, mesh=mesh,
                            embed_fused=True, interpret=True)
        lab_bf, _ = mb_f.assign_batch(Xq)
        assert np.array_equal(lab_bf, np.asarray(lab1)), kernel
        ab = AsyncBatcher(m, max_wait_ms=5.0, max_bucket=64, mesh=mesh,
                          embed_fused=True, interpret=True)
        futs = [ab.submit(np.asarray(Xq[:, i:i + 25]))
                for i in range(0, 101, 25)]
        ab.flush()
        lab_a = np.concatenate([f.result()[0] for f in futs])
        assert np.array_equal(lab_a, np.asarray(lab1)), kernel
    print("sharded_extend ok (two-pass + fused)")


if __name__ == "__main__":
    check_sharded_extend()
    check_distributed_fwht()
    check_dfwht_on_2d_mesh()
    check_sketched_allreduce_pmean()
    check_sharded_vs_single_device_loss()
    check_sharded_train_step()
    print("ALL DIST CHECKS PASSED")
