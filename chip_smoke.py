"""Smoke run of the DMuon trainer and server on a TPU, through the library
entry points a user calls, at smollm-360m's published widths (32 layers,
d_model 960, 15 heads with 5 KV heads, d_ff 2560, vocab 49152) with random
weights made from ``--seed``.

    python chip_smoke.py              # one chip: kernels, muon, adamw, serve
    python chip_smoke.py --chips 4    # four chips: owner vs gather on a mesh

Each phase prints its own line; a failed check raises and the script exits
non-zero.  The last line, printed only when every phase passed, is one JSON
object naming the device as JAX reports it.  Without a TPU the script exits
non-zero before any phase.

``--cpu-rehearsal`` runs the same phases on the CPU backend at the reduced
config (two layers, d_model 64), with the kernels interpreted.  It checks
paths and control flow only; it never prints ``"ok"``.  For the mesh phase:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python chip_smoke.py --cpu-rehearsal --chips 4
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-360m"
SEQ, BATCH, STEPS = 2048, 4, 8      # batch 4 x 2048 fits one 16 GiB v5e
MESH_STEPS = 4

# Gram NS through the kernels, at the ambient default precision, against the
# jnp path under "highest": every product of both asks for HIGHEST itself.  Max
# |kernel - ref| over max |ref|; fp32 rounding in a different order stays
# near 1e-5.  A kernel that multiplied its fp32 blocks in one bf16 pass
# (2^-9 relative rounding) would be near 1e-2 after 5 NS steps; a missing,
# misplaced or unmirrored block is wrong by the size of the entries.
KERNEL_TOL = 1e-3
# The first step's loss of a small-weight init is that of a uniform guess,
# ln(vocab); random weights move it by a few hundredths.
LOSS0_TOL = 0.5
# Served logits against a solo decode of the same prompt, over the solo
# logits' max magnitude, both at "highest" matmul precision.  The two are
# programs of different batch shape and round differently in fp32 (~1e-6);
# a cache row written to the wrong slot, or a mask reading past a slot's
# position, moves the logits by their own size.  At the default precision
# the activations are rounded to bf16 in every layer, and the two programs
# drift apart by up to 1e-2 of scale over 32 layers, which would hide such
# a fault among near-tied tokens.
SERVE_TOL = 1e-3
# Owner-mode against gather-mode parameters after MESH_STEPS steps on the
# same batches, over the largest parameter change, both at "highest" matmul
# precision.  Owner mode runs Gram NS on owner stacks, gather mode standard
# NS on every matrix; in fp32 they differ by rounding (1.6e-4 of the update
# at the reduced config on the CPU).  At the default precision standard NS
# rounds its products to bf16, so the comparison would measure that.  A
# misrouted shard is wrong by the whole update.
MESH_TOL = 5e-3


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def gib(n) -> str:
    return f"{n / 2**30:.3f} GiB"


# ------------------------------------------------------------------ phases


def phase_kernels(cfg, seed: int, rehearsal: bool) -> None:
    """gram_newton_schulz through the Pallas kernels at the model's Gram
    group shapes and stack counts, against the jnp path."""
    import jax
    import numpy as np

    from repro.core import api
    from repro.core.gram_ns import GramNSConfig, gram_newton_schulz
    from repro.kernels import interpret
    from repro.models import model_fns

    shapes = jax.eval_shape(lambda k: model_fns(cfg).init(cfg, k),
                            jax.random.PRNGKey(seed))
    plan = api.dedicate_params(shapes, num_owners=1)
    groups = sorted({g.key: g.count for g in plan.groups.values()}.items())
    kern = GramNSConfig(use_kernels=True)
    for (m, n), count in groups:
        x = jax.random.normal(jax.random.PRNGKey(seed), (count, m, n))
        fk = jax.jit(lambda a: gram_newton_schulz(
            a, kern, assume_short_fat=True))
        fj = jax.jit(lambda a: gram_newton_schulz(
            a, GramNSConfig(), assume_short_fat=True))
        calls = fk.lower(x).compile().as_text().count("tpu_custom_call")
        got = np.asarray(fk(x))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(fj(x))
        check(np.isfinite(got).all(), f"kernels ({m},{n}): non-finite output")
        err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        print(f"[kernels] ({count},{m},{n}) interpret={interpret()} "
              f"tpu_custom_calls={calls} max_err/max_ref={err:.3e} "
              f"tol={KERNEL_TOL}", flush=True)
        check(rehearsal or (not interpret() and calls > 0),
              f"kernels ({m},{n}) did not compile to Mosaic custom calls")
        check(err <= KERNEL_TOL, f"kernels ({m},{n}): error {err} > tol")


def phase_train(cfg, mode: str, seq: int, batch: int, steps: int,
                seed: int) -> None:
    """``steps`` steps of ``mode`` through ResilientLoop on one device."""
    import jax
    import numpy as np

    from repro.core.muon import MuonConfig
    from repro.data.pipeline import DataConfig
    from repro.runtime.resilient import ResilientConfig, ResilientLoop

    loop = ResilientLoop(
        cfg, DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                        seed=seed),
        muon=MuonConfig(mode=mode),
        run=ResilientConfig(steps=steps, seed=seed), num_owners=1)
    tok = jax.ShapeDtypeStruct((batch, seq), np.int32)
    mem = loop.step_fn.lower(loop.state, {"tokens": tok, "labels": tok}) \
        .compile().memory_analysis()
    if mem is not None:
        print(f"[train {mode}] compiled step: arguments "
              f"{gib(mem.argument_size_in_bytes)} outputs "
              f"{gib(mem.output_size_in_bytes)} aliased "
              f"{gib(mem.alias_size_in_bytes)} temp "
              f"{gib(mem.temp_size_in_bytes)}", flush=True)
    report = loop.run()
    losses = report.loss_curve()
    name = "muon (owner)" if mode == "owner" else mode
    stats = jax.devices()[0].memory_stats()
    mem_line = "not reported" if not stats else ", ".join(
        f"{k} {gib(v)}" for k, v in sorted(stats.items()) if "bytes" in k)
    print(f"[train {name}] batch {batch} x seq {seq}, {report.steps} steps, "
          f"loss_ema {[round(x, 4) for x in losses]}, step wall s "
          f"{[round(t, 3) for t in report.step_times]}", flush=True)
    print(f"[train {name}] device memory_stats after the steps: {mem_line}",
          flush=True)
    check(report.steps == steps, f"{mode}: ran {report.steps} steps")
    check(all(math.isfinite(x) for x in losses), f"{mode}: non-finite loss")
    check(abs(losses[0] - math.log(cfg.vocab)) <= LOSS0_TOL,
          f"{mode}: first loss {losses[0]} not near ln(vocab) "
          f"{math.log(cfg.vocab):.4f}")


def phase_serve(cfg, seed: int) -> None:
    """A few requests through serve.Scheduler, each replayed solo.  Called
    under "highest" matmul precision (see SERVE_TOL)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model_fns
    from repro.serve import Request, RequestQueue, Scheduler, ServeConfig
    from repro.train import serve as serve_fns

    params = jax.jit(lambda k: model_fns(cfg).init(cfg, k))(
        jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, tokens=rng.integers(0, cfg.vocab, 16,
                                               dtype=np.int32),
                    max_new_tokens=int(n))
            for i, n in enumerate((4, 8, 6, 8, 5))]
    scfg = ServeConfig(num_slots=4, max_len=64, prefill_pack=2,
                       cache_dtype=jnp.float32, record_logits=True)
    sched = Scheduler(cfg, params, scfg)
    metrics = sched.run(RequestQueue(reqs))
    check(len(metrics.requests) == len(reqs), "serve: requests lost")

    prefill = jax.jit(lambda p, t: serve_fns.prefill_fn(
        cfg, p, t, scfg.max_len, cache_dtype=jnp.float32))
    decode = jax.jit(lambda p, t, c, pos: serve_fns.decode_fn(
        cfg, p, t, c, pos))
    worst, ties, tokens = 0.0, 0, 0
    for req in reqs:
        rec = metrics.requests[req.rid]
        check(rec.generated == req.max_new_tokens,
              f"serve: rid {req.rid} generated {rec.generated}")
        logits, cache = prefill(params, jnp.asarray(req.tokens)[None])
        for i, served in enumerate(rec.logits):
            if i:   # feed the served token: the solo run follows its stream
                logits, cache = decode(
                    params, jnp.asarray([rec.tokens[i - 1]], jnp.int32),
                    cache, jnp.asarray(req.prompt_len + i - 1, jnp.int32))
            solo = np.asarray(logits[0], np.float32)
            scale = float(np.max(np.abs(solo)))
            err = float(np.max(np.abs(np.asarray(served) - solo))) / scale
            worst = max(worst, err)
            check(err <= SERVE_TOL,
                  f"serve: rid {req.rid} token {i}: logits error {err}")
            top2 = np.sort(solo)[-2:]
            if rec.tokens[i] != int(np.argmax(solo)):
                # only a near-tie, closer than the tolerance, may flip
                check(top2[1] - top2[0] <= 2 * SERVE_TOL * scale,
                      f"serve: rid {req.rid} token {i}: served "
                      f"{rec.tokens[i]} != solo {int(np.argmax(solo))}")
                ties += 1
            tokens += 1
    print(f"[serve] {len(reqs)} requests, {tokens} tokens through 4 slots "
          f"(donated caches, highest precision): greedy tokens match solo "
          f"decode ({ties} near-ties), max logit error/scale {worst:.3e} "
          f"tol={SERVE_TOL}", flush=True)


def phase_mesh(cfg, seq: int, batch: int, seed: int) -> None:
    """Owner-sharded DMuon against gather-mode Muon-AG on a mesh over four
    devices, built as ``launch/train.py --mesh`` builds it.  Called under
    "highest" matmul precision (see MESH_TOL)."""
    import jax
    import numpy as np

    from repro.core.muon import MuonConfig
    from repro.data.pipeline import DataConfig
    from repro.runtime.elastic import remesh
    from repro.runtime.resilient import ResilientConfig, ResilientLoop

    mesh = remesh(jax.devices()[:4])
    print(f"[mesh] shape {dict(mesh.shape)} over "
          f"{[d.id for d in mesh.devices.flat]}", flush=True)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                      seed=seed)
    params, init = {}, None
    for mode in ("owner", "gather"):
        loop = ResilientLoop(cfg, dcfg, muon=MuonConfig(mode=mode),
                             run=ResilientConfig(steps=MESH_STEPS, seed=seed),
                             mesh=mesh)
        if init is None:
            init = jax.device_get(loop.state.params)
        report = loop.run()
        losses = report.loss_curve()
        check(all(math.isfinite(x) for x in losses),
              f"mesh {mode}: non-finite loss")
        print(f"[mesh {mode}] {report.steps} steps, loss_ema "
              f"{[round(x, 4) for x in losses]}", flush=True)
        for leaf in jax.tree.leaves(loop.state):
            check(len(leaf.sharding.device_set) == 4,
                  f"mesh {mode}: a state leaf lives on "
                  f"{len(leaf.sharding.device_set)} device(s)")
        if mode == "owner":
            for key, buf in loop.state.opt_state.momentum.items():
                devs = {s.device for s in buf.addressable_shards}
                rows = {s.data.shape[0] for s in buf.addressable_shards}
                check(len(devs) == 4 and rows == {buf.shape[0] // 4},
                      f"momentum {key}: shards on {len(devs)} devices, "
                      f"rows {rows} of {buf.shape[0]}")
            print(f"[mesh owner] {len(loop.state.opt_state.momentum)} "
                  f"momentum stacks, each split row-wise over 4 devices",
                  flush=True)
        params[mode] = jax.device_get(loop.state.params)
        del loop
        gc.collect()

    diff = max(float(np.max(np.abs(a - b))) for a, b in zip(
        jax.tree.leaves(params["owner"]), jax.tree.leaves(params["gather"])))
    step = max(float(np.max(np.abs(a - b))) for a, b in zip(
        jax.tree.leaves(params["owner"]), jax.tree.leaves(init)))
    print(f"[mesh] owner vs gather params: max diff {diff:.3e} over max "
          f"update {step:.3e} = {diff / step:.3e} tol={MESH_TOL}", flush=True)
    check(step > 0, "mesh: parameters did not move")
    check(diff / step <= MESH_TOL, "mesh: owner and gather disagree")


# -------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the owner-vs-gather mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="reduced config on the CPU backend (never on a chip)")
    args = ap.parse_args()

    import jax

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if dev.platform != want:
        print(f"chip_smoke: needs platform {want!r}, JAX found "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"[device] {device} compile cache {cache}", flush=True)

    cfg = configs.get(ARCH, reduced=args.cpu_rehearsal)
    seq, batch, steps = (64, 2, 3) if args.cpu_rehearsal \
        else (SEQ, BATCH, STEPS)
    print(f"[config] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}", flush=True)
    if args.chips == 4:
        with jax.default_matmul_precision("highest"):
            phase_mesh(cfg, seq, batch, args.seed)
    else:
        phase_kernels(cfg, args.seed, args.cpu_rehearsal)
        phase_train(cfg, "owner", seq, batch, steps, args.seed)
        gc.collect()
        phase_train(cfg, "adamw", seq, batch, steps, args.seed)
        gc.collect()
        with jax.default_matmul_precision("highest"):
            phase_serve(cfg, args.seed)

    if args.cpu_rehearsal:
        print(json.dumps({"rehearsal_passed": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
