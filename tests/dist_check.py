"""Multi-device parity check, run in a subprocess with 8 host devices.

Invoked by tests/test_distributed.py as:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 python tests/dist_check.py

Checks, on a (2, 4) ('data','model') mesh:
  1. owner-mode DMuon inside jit under the mesh == single-device gather mode
     (exact optimizer semantics under sharding, the paper's core invariant);
  2. the owner-layout momentum state is actually sharded over all 8 devices
     (ZeRO-like state sharding: per-device bytes = total / 8);
  3. the lowered HLO of the owner step contains reduce-scatter/all-to-all
     style collectives rather than a full all-gather of every gradient plus
     replicated NS (structural check of the communication pattern);
  4. sharded AdamW path still works for non-matrix leaves.
Later cases (numbered in ``main``) cover the bucketed pipelines, the Pallas
kernels inside the owner-local shard_map and attention's kernel dispatch.
"""

import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""), \
    "run via test_distributed.py"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import api
from repro.core.gram_ns import GramNSConfig
from repro.core.muon import MuonConfig
from repro.models import layers
from repro.runtime.elastic import make_mesh


def tree(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {
        "blocks": {
            "wq": jax.random.normal(ks[0], (8, 64, 64)) * 0.02,
            "wo": jax.random.normal(ks[1], (8, 64, 64)) * 0.02,
            "up": jax.random.normal(ks[2], (8, 64, 256)) * 0.02,
            "down": jax.random.normal(ks[3], (8, 256, 64)) * 0.02,
            "norm_scale": jnp.ones((8, 64)),
        },
        "embed_table": jax.random.normal(ks[4], (128, 64)) * 0.02,
    }


def main():
    assert jax.device_count() == 8, jax.device_count()
    mesh = make_mesh((2, 4), ("data", "model"))

    params = tree()
    grads = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(1), x.shape) * 0.1,
        params)

    # training shardings: TP on the hidden axes, replicated elsewhere
    specs = {
        "blocks": {
            "wq": P(None, None, "model"), "wo": P(None, "model", None),
            "up": P(None, None, "model"), "down": P(None, "model", None),
            "norm_scale": P(None, None),
        },
        "embed_table": P("model", None),
    }
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    params_sh = jax.device_put(params, shardings)
    grads_sh = jax.device_put(grads, shardings)

    plan = api.dedicate_params(params, mesh=mesh, strategy="greedy")
    cfg = MuonConfig(mode="owner", learning_rate=0.1, momentum=0.9,
                     ns=GramNSConfig(num_steps=5))
    opt = api.Muon(plan, mesh=mesh, config=cfg)

    state = jax.jit(opt.init)(params_sh)

    # (2) momentum buffers sharded over all devices along the stack axis
    for key, buf in state.momentum.items():
        nshards = len({d for s in buf.addressable_shards for d in [s.device]})
        assert nshards == 8, (key, nshards)
        shard_rows = buf.addressable_shards[0].data.shape[0]
        assert shard_rows == buf.shape[0] // 8, (key, shard_rows, buf.shape)
    print("momentum sharding: OK")

    step = jax.jit(opt.update)
    lowered = step.lower(grads_sh, state, params_sh)
    hlo = lowered.compile().as_text()

    # (3) communication pattern: owner transposes are all-to-all/reduce-
    # scatter/collective-permute + publish all-gathers; vanilla Muon-AG would
    # need one all-gather per matrix leaf plus replicated NS.
    has_comm = any(op in hlo for op in
                   ("all-to-all", "reduce-scatter", "collective-permute",
                    "all-gather"))
    assert has_comm, "expected collectives in owner-mode step"
    print("owner-mode collectives present: OK")

    updates_sh, state2 = step(grads_sh, state, params_sh)

    # (1) parity with single-device gather mode
    plan1 = api.dedicate_params(params, num_owners=1, strategy="rank0")
    opt1 = api.Muon(plan1, config=MuonConfig(
        mode="gather", learning_rate=0.1, momentum=0.9,
        ns=GramNSConfig(num_steps=5)))
    s1 = opt1.init(params)
    updates1, _ = opt1.update(grads, s1, params)

    flat_sh = jax.tree_util.tree_leaves_with_path(updates_sh)
    flat_1 = {"/".join(str(getattr(k, 'key', k)) for k in kp): v
              for kp, v in jax.tree_util.tree_leaves_with_path(updates1)}
    for kp, v in flat_sh:
        path = "/".join(str(getattr(k, 'key', k)) for k in kp)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(v), dtype=np.float32),
            np.asarray(flat_1[path], dtype=np.float32),
            rtol=5e-3, atol=5e-4, err_msg=path)
    print("owner(8 devices) == gather(1 device): OK")

    # (4) second step runs and step counter advances
    _, state3 = step(grads_sh, state2, params_sh)
    assert int(state3.step) == 2

    # (5) bucket-fused Gram iteration under the mesh == per-group path
    opt_f = api.Muon(plan, mesh=mesh, config=MuonConfig(
        mode="owner", learning_rate=0.1, momentum=0.9,
        ns=GramNSConfig(num_steps=5, bucket_fusion=True)))
    sf = jax.jit(opt_f.init)(params_sh)
    uf, _ = jax.jit(opt_f.update)(grads_sh, sf, params_sh)
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(updates_sh),
            jax.tree_util.tree_leaves_with_path(uf)):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(a), np.float32),
            np.asarray(jax.device_get(b), np.float32),
            rtol=1e-4, atol=1e-5)
    print("bucket fusion under mesh: OK")

    # (6) bucketed pipeline schedule under the mesh == fused schedule.
    # This is the only place the optimization_barrier ties are live (they
    # are gated off on a single device), so parity here pins down that the
    # schedule reordering + barriers change no values.
    import dataclasses
    opt_b = api.Muon(plan, mesh=mesh,
                     config=dataclasses.replace(cfg, pipeline="bucketed"))
    ub, _ = jax.jit(opt_b.update)(grads_sh, state, params_sh)
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(updates_sh),
            jax.tree_util.tree_leaves_with_path(ub)):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(a), np.float32),
            np.asarray(jax.device_get(b), np.float32),
            rtol=1e-5, atol=1e-6,
            err_msg="/".join(str(getattr(k, 'key', k)) for k in kp))
    print("bucketed pipeline under mesh: OK")

    # (7) pre-staged entry point under the mesh: accumulating packed
    # per-microbatch gradients in the owner layout == packing the averaged
    # gradient (the accumulation-overlap schedule, docs/DESIGN.md §6).
    from repro.core.muon import _matrix_and_rest
    from repro.core.pipeline import BucketPipeline
    pipe = BucketPipeline(plan, opt_b.config, mesh, opt_b.variant)
    g2 = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(7), x.shape) * 0.1,
        params)
    g2_sh = jax.device_put(g2, shardings)

    def prestage_step(ga, gb, st, pm):
        ga_m, ga_r, _ = _matrix_and_rest(plan, ga)
        gb_m, gb_r, _ = _matrix_and_rest(plan, gb)
        sa = pipe.stage_in_all(ga_m, dtype=jnp.float32)
        sb2 = pipe.stage_in_all(gb_m, dtype=jnp.float32)
        staged = {k: (sa[k] + sb2[k]) * 0.5 for k in sa}
        rest = {p: (ga_r[p] + gb_r[p]) * 0.5 for p in ga_r}
        return opt_b.update_staged(staged, rest, st, pm)

    avg = jax.tree.map(lambda a, b: (a + b) * 0.5, grads_sh, g2_sh)
    u_ref, _ = jax.jit(opt_b.update)(avg, state, params_sh)
    u_pre, _ = jax.jit(prestage_step)(grads_sh, g2_sh, state, params_sh)
    flat_ref = {"/".join(str(getattr(k, 'key', k)) for k in kp): v
                for kp, v in jax.tree_util.tree_leaves_with_path(u_ref)}
    for kp, v in jax.tree_util.tree_leaves_with_path(u_pre):
        path = "/".join(str(getattr(k, 'key', k)) for k in kp)
        # not bit-exact across these two program shapes: XLA fuses the NS
        # dots differently, and 5 NS iterations amplify the 1-ulp input
        # rounding; single-device bit-exactness is pinned in
        # tests/test_pipeline.py
        np.testing.assert_allclose(
            np.asarray(jax.device_get(v), np.float32),
            np.asarray(jax.device_get(flat_ref[path]), np.float32),
            rtol=1e-3, atol=1e-5, err_msg=path)
    print("pre-staged accumulation under mesh: OK")

    # (8) the Pallas kernels inside the owner-local shard_map: jax.shard_map
    # checks varying manual axes (vma) by default, and must accept the
    # pallas_calls Gram NS issues there.  Kernel blocks sum in another order
    # than the jnp dots: same tolerance as test_kernels' end-to-end check.
    opt_k = api.Muon(plan, mesh=mesh, config=dataclasses.replace(
        cfg, ns=GramNSConfig(num_steps=5, use_kernels=True)))
    uk, _ = jax.jit(opt_k.update)(grads_sh, state, params_sh)
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(updates_sh),
            jax.tree_util.tree_leaves_with_path(uk)):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(a), np.float32),
            np.asarray(jax.device_get(b), np.float32),
            rtol=1e-4, atol=1e-4,
            err_msg="/".join(str(getattr(k, 'key', k)) for k in kp))
    print("Pallas kernels inside shard_map under mesh: OK")

    # (9) long causal self-attention keeps the chunked online softmax when
    # its inputs are split over the mesh: a Pallas call there would need a
    # shard_map.  One device's trace holds the kernel (in its TPU branch).
    acfg = layers.AttnConfig(d_model=64, n_heads=6, n_kv_heads=2,
                             head_dim=64)
    ap = layers.attention_init(jax.random.PRNGKey(2), acfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 2048, 64))

    def attn_grad(p, x):
        return jax.grad(lambda p, x: jnp.sum(
            layers.attention(p, acfg, x)[0]))(p, x)
    ap_sh = jax.device_put(ap, NamedSharding(mesh, P()))
    x_sh = jax.device_put(x, NamedSharding(mesh, P("data")))
    assert "pallas_call" not in str(jax.make_jaxpr(attn_grad)(ap_sh, x_sh))
    assert "pallas_call" in str(jax.make_jaxpr(attn_grad)(ap, x))
    print("attention under mesh keeps the chunked path: OK")
    print("ALL DISTRIBUTED CHECKS PASSED")


if __name__ == "__main__":
    main()
