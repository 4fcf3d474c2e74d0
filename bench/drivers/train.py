"""Training cells: the program's own loop, timed, traced and checked.

Set-up builds one ``ResilientLoop`` (the loop ``launch/train.py`` runs), at
the cell's configuration and optimizer, with its weights made on the device
from the seed.  Its step (``make_train_step``: forward, backward and the
optimizer in one jit, state donated) is compiled once, ahead of time, for
the cell's batch; the loop then calls that executable, so nothing compiles
later.  The benchmark's own traffic enters through the loop's ``pipe`` seam.

The first ``check_steps`` steps go through ``loop.run()`` on distinct rows
and are read for the comparison: each step's loss, each leaf's first
gradient (from the optimizer state after step one) and each leaf's change
after the last check step.  The same loop then runs the measured window.
Once the window has closed, memory is read and the program's state freed,
the plain reference runs the same check steps from the same seed.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import math
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

from bench import peaks, trace as tr
from bench.traffic import ZipfTokens

# The comparison's numbers, in the order they are printed.  A cell compares
# those its limits file (bench/limits/<cell>.json) names.
CHECKS = ("loss_gap", "grad_norm_gap", "update_norm_gap", "update_dir_gap")
# Faults planted in the program for the checks' own tests and calibration
# (see ``plant``).
FAULTS = ("bf16", "half_batch", "frozen", "swap", "ns_high", "ns_default",
          "ns_bf16", "shard_ns")
# Leaves whose reference gradient is below this share of the median leaf's
# (a key bias under softmax) move under AdamW by round-off alone; they are
# left out of the change comparison.
ZERO_GRAD_SHARE = 1e-3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Feed:
    """The loop's data seam (``__next__``, ``close``, ``state``): batch k of
    the cell's traffic, placed as ``data/pipeline.Pipeline._place`` places
    a batch without an explicit sharding.  Batches are made ahead
    (``prepare``), as the program's pipeline makes them in a background
    thread, so that only placement runs in the loop.  ``seek`` reopens the
    stream the loop closes at the end of every ``run()``."""

    def __init__(self, gen: ZipfTokens, rows: int | None = None):
        self.gen = gen
        self.rows = rows
        self.step = 0
        self.closed = False
        self.ready: dict = {}

    def prepare(self, start: int, stop: int) -> None:
        self.ready = {k: self.gen.batch_at(k) for k in range(start, stop)}

    def __iter__(self):
        return self

    def __next__(self) -> dict:
        import jax
        if self.closed:
            raise StopIteration
        with jax.profiler.TraceAnnotation("bench.feed"):
            batch = self.ready.pop(self.step, None) or \
                self.gen.batch_at(self.step)
            self.step += 1
            return {k: jax.numpy.asarray(v[:self.rows])
                    for k, v in batch.items()}

    def close(self) -> None:
        self.closed = True

    def seek(self, step: int) -> None:
        self.step, self.closed = step, False

    def state(self) -> dict:
        return {"data_step": np.asarray(self.step, np.int64)}


def program_config(conf: dict, fault: str | None):
    """The program's ``ArchConfig`` for a configuration file, checked
    against the file's published sizes."""
    from repro import configs
    prog = conf["program"]
    arch = configs.get(prog["arch"], reduced=bool(prog.get("reduced")))
    overrides = dict(prog.get("overrides", {}))
    if fault == "bf16":   # the program's own bfloat16 compute path
        overrides["compute_dtype"] = "bfloat16"
    arch = dataclasses.replace(arch, **overrides)
    want = {"d_model": conf["hidden_size"], "d_ff": conf["intermediate_size"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "n_layers": conf["num_hidden_layers"],
            "vocab": conf["vocab_size"], "norm_eps": conf["rms_norm_eps"],
            "rope_theta": conf["rope_theta"],
            "tie_embeddings": conf["tie_word_embeddings"],
            "qkv_bias": bool(conf.get("attention_bias")),
            "hd": conf.get("head_dim") or conf["hidden_size"]
            // conf["num_attention_heads"]}
    got = {k: getattr(arch, k) for k in want}
    if got != want:
        raise ValueError(f"program config {got} departs from the "
                         f"configuration file {want}")
    return arch


# ResilientLoop makes its state with train/step.init_state, whose jit closes
# over the PRNG key: each new seed is a new program, compiled anew (about 20 s
# for smollm-360m).  The loop is built at this fixed seed, whose program the
# cache holds, and its state is then made again from the run's seed by
# ``seeded_state``, with the key an argument of the jit.
LOOP_SEED = 0


def seeded_state(loop, arch, seed: int):
    """The loop's state as ``init_state`` makes it (the model's init, then
    the optimizer's), from ``seed``, on the loop's own shardings.  Its tree,
    shapes and dtypes must equal those of the state ``init_state`` made, so
    that a change there stops the run instead of drifting from it."""
    import jax
    import jax.numpy as jnp

    from repro.models import model_fns
    from repro.train.train_state import TrainState
    init = model_fns(arch).init

    def build(key):
        params = init(arch, key)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=loop.opt.init(params),
                          loss_ema=jnp.zeros((), jnp.float32))

    def signature(tree):
        leaves, treedef = jax.tree.flatten(tree)
        return treedef, [(x.shape, x.dtype) for x in leaves]

    key = jax.random.PRNGKey(seed)
    if signature(jax.eval_shape(build, key)) != signature(loop.state):
        raise RuntimeError("seeded_state departs from train/step.init_state"
                           "'s tree; rebuild it from init_state")
    shardings = jax.tree.map(lambda x: x.sharding, loop.state)
    loop.state = None                  # free the fixed seed's state first
    return jax.jit(build, out_shardings=shardings)(key)


def flat(tree) -> dict:
    import jax
    return {"/".join(str(getattr(k, "key", k)) for k in kp): leaf
            for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _norms(tree: dict) -> dict:
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})
    return {k: float(v) for k, v in fn(tree).items()}


def _gap_norms(a: dict, b: dict) -> dict:
    """``|a[k] - b[k]|`` for each leaf ``k`` of ``b``, on the device, one
    leaf at a time (``a`` may hold host arrays)."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))))
    return {k: float(fn(jnp.asarray(a[k]), v)) if k in a else math.inf
            for k, v in b.items()}


def first_grad_norms(opt_state, adam_b1: float) -> dict:
    """Each leaf's first gradient norm, from the optimizer state after one
    step from zero state: Muon's momentum is the gradient itself (owner
    stacks are zero-padded and keyed by the leaf path with '.' for '/');
    AdamW's first moment is ``(1 - b1)`` times it."""
    out = {k.replace(".", "/"): v
           for k, v in _norms(dict(opt_state.momentum)).items()}
    for k, v in _norms(dict(opt_state.adamw.mu)).items():
        out[k] = v / (1.0 - adam_b1)
    return out


def losses_from_ema(emas) -> list:
    """Per-step losses from the loop's ``loss_ema`` (``ema_0 = loss_0``,
    ``ema_k = 0.98 ema_{k-1} + 0.02 loss_k``, train/step.py)."""
    out = [emas[0]]
    for prev, cur in zip(emas, emas[1:]):
        out.append((cur - 0.98 * prev) / 0.02)
    return out


def _gap(a: float, b: float, scale: float) -> float:
    g = abs(a - b) / scale
    return g if math.isfinite(g) else math.inf


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct`` (PERF.md, section 2).  ``prog``
    and ``ref`` each hold the check steps' ``losses``, each leaf's first
    gradient norm (``grad_norms``), and each leaf's change over the check
    steps (``deltas``) with its norm (``delta_norms``)."""
    loss = max((_gap(a, b, 1.0) for a, b in zip(prog["losses"],
                                                ref["losses"])),
               default=math.inf)
    if len(prog["losses"]) != len(ref["losses"]):
        loss = math.inf
    rg, pg = ref["grad_norms"], prog["grad_norms"]
    med_g = statistics.median(rg.values())
    kept = [k for k, v in rg.items() if v >= ZERO_GRAD_SHARE * med_g]
    rd, pd = ref["delta_norms"], prog["delta_norms"]
    med_d = statistics.median(rd[k] for k in kept)
    g_gaps = {k: _gap(pg.get(k, math.inf), v, max(v, med_g))
              for k, v in rg.items()}
    d_gaps = {k: _gap(pd.get(k, math.inf), rd[k], max(rd[k], med_d))
              for k in kept}
    apart = _gap_norms(prog["deltas"], {k: ref["deltas"][k] for k in kept})
    a_gaps = {k: _gap(apart[k], 0.0, max(rd[k], med_d)) for k in kept}
    worst = lambda gaps: sorted(gaps.items(), key=lambda kv: -kv[1])[:3]
    log(f"[compare] worst leaves: gradient {worst(g_gaps)} change "
        f"{worst(d_gaps)} direction {worst(a_gaps)}; left out "
        f"{sorted(set(rg) - set(kept))}")
    grad = max(g_gaps.values()) if set(pg) == set(rg) else math.inf
    return {"loss_gap": loss, "grad_norm_gap": grad,
            "update_norm_gap": max(d_gaps.values()),
            "update_dir_gap": max(a_gaps.values())}


def plant(loop, fault: str | None):
    """Plant ``fault`` in the program, before its step is traced; returns
    a function that takes it out again.

    ``frozen``: the step computes the loss and returns parameters and
    optimizer state unchanged.  ``swap``: the q_proj and o_proj updates
    trade places, a misrouting that keeps every norm.  ``ns_high`` and
    ``ns_default``: every Gram Newton-Schulz product at ``Precision.HIGH``
    (three bfloat16 passes) or the default (one), below the configuration's
    HIGHEST.  ``shard_ns`` (a mesh only): each chip's block of each Muon
    matrix, as the training layout splits it over the mesh, is
    orthogonalized on its own, in place of the whole matrix at its owner:
    the update of a Muon that leaves out the exchange between chips.
    ``bf16``, ``ns_bf16`` and ``half_batch`` are set in ``run`` through the
    program's own options and the feed."""
    import jax
    import jax.numpy as jnp
    opt = loop.opt
    if fault == "frozen":
        opt.update = lambda grads, state, params: (
            jax.tree.map(jnp.zeros_like, params), state)
    elif fault == "swap":
        update = opt.update

        def swapped(grads, state, params):
            u, state = update(grads, state, params)
            a = u["blocks"]["attn"]
            if a["q_proj"]["w"].shape != a["o_proj"]["w"].shape:
                raise ValueError("swap needs square attention projections")
            a["q_proj"]["w"], a["o_proj"]["w"] = (a["o_proj"]["w"],
                                                  a["q_proj"]["w"])
            return u, state
        opt.update = swapped
    elif fault in ("ns_high", "ns_default"):
        from repro.core import gram_ns
        from repro.kernels import ref
        precision = (jax.lax.Precision.HIGH if fault == "ns_high"
                     else jax.lax.Precision.DEFAULT)

        def bmm(a, b):
            out = jax.lax.dot_general(
                a, b, (((a.ndim - 1,), (b.ndim - 2,)),
                       (tuple(range(a.ndim - 2)), tuple(range(b.ndim - 2)))),
                precision=precision, preferred_element_type=jnp.float32)
            return out.astype(a.dtype)
        kept = ref.bmm, gram_ns.bmm
        ref.bmm = gram_ns.bmm = bmm

        def restore():
            ref.bmm, gram_ns.bmm = kept
        return restore
    elif fault == "shard_ns":
        from repro.core import muon
        from repro.core.owner_comms import group_key_str
        if loop.mesh is None:
            raise ValueError("shard_ns needs a mesh")
        # which axis of each owner stack's (m, n) matrices the training
        # layout splits, and over how many chips
        specs = {k: v.sharding.spec
                 for k, v in flat(loop.state.params).items()}
        split = {}
        for path in opt.plan.groups:
            info = opt.plan.leaves[path]
            spec = tuple(specs[path]) + (None,) * (len(info.shape)
                                                   - len(specs[path]))
            dims = spec[-2:][::-1] if info.transpose else spec[-2:]
            for axis, names in ((1, dims[0]), (2, dims[1])):
                if names is not None:
                    names = names if isinstance(names, tuple) else (names,)
                    split[group_key_str(path)] = (axis, math.prod(
                        loop.mesh.shape[a] for a in names))
                    break
        if not split:
            raise ValueError("shard_ns: no Muon matrix is split over the mesh")
        kept = muon.make_orthogonalizer
        muon.make_orthogonalizer = lambda name, cfg: functools.partial(
            _shard_ns, split=split)

        def restore():
            muon.make_orthogonalizer = kept
        return restore
    return lambda: None


def _shard_ns(stacks, *, step, state, layout, cfg, split):
    """The ``shard_ns`` fault's orthogonalizer (see ``plant``): Gram
    Newton-Schulz of each block of each owner stack that the training layout
    gives one chip; ``split`` maps a stack to ``(axis, chips)``."""
    from repro.core.gram_ns import gram_newton_schulz

    def blocks(x, how):
        if how is None:
            return gram_newton_schulz(x, cfg.ns)
        axis, k = how
        c, m, n = x.shape
        if axis == 1:
            o = gram_newton_schulz(x.reshape(c * k, m // k, n), cfg.ns)
            return o.reshape(c, m, n)
        b = x.reshape(c, m, k, n // k).transpose(0, 2, 1, 3)
        o = gram_newton_schulz(b.reshape(c * k, m, n // k), cfg.ns)
        return o.reshape(c, k, m, n // k).transpose(0, 2, 1, 3).reshape(
            c, m, n)

    return layout.shard_local(
        lambda sts: {s: blocks(x, split.get(s)) for s, x in sts.items()},
        stacks), state


class CompileCounter:
    """Counts backend compilations while ``on``."""

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, duration, **kw):
        if self.on and "backend_compile" in name:
            self.count += 1


def run(cell: dict, seed: int, seconds: float, trace: bool, t0: float, *,
        fault: str | None = None, window: bool = True,
        ref: dict | None = None) -> dict:
    """One run of a training cell; returns the result line as a dict.

    ``fault`` (one of ``FAULTS``) changes the program for the checks' own
    tests and calibration: ``"bf16"`` runs its bfloat16 compute path,
    ``"ns_bf16"`` its Gram Newton-Schulz in bfloat16, ``"half_batch"``
    feeds the step the first half of each batch, and the others are
    planted by ``plant``.  ``window=False`` stops after the check steps and
    the comparison.  ``ref``: the reference's readings of this seed, made
    beforehand (calibration compares one seed under several faults)."""
    import jax
    import jax.numpy as jnp

    from repro.core.muon import MuonConfig
    from repro.data.pipeline import DataConfig
    from repro.runtime.elastic import remesh
    from repro.runtime.resilient import ResilientConfig, ResilientLoop

    conf, traffic = cell["config"], cell["traffic"]
    ref_mod = cell["reference"]
    arch = program_config(conf, fault)
    B, S = int(traffic["batch"]), int(traffic["seq_len"])
    n_check = int(traffic["check_steps"])
    chips = int(cell["chips"])
    devices = jax.devices()[:chips]
    opt = traffic["optimizer"]
    if opt.get("variant", "muon") not in ("muon", "adamw"):
        raise ValueError("the reference covers the muon and adamw variants")
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    gen = ZipfTokens(traffic, conf, seed)
    muon = MuonConfig(**opt)
    if fault == "ns_bf16":
        muon = dataclasses.replace(muon, ns=dataclasses.replace(
            muon.ns, compute_dtype="bfloat16"))

    loop = ResilientLoop(
        arch, DataConfig(vocab=arch.vocab, seq_len=S, global_batch=B,
                         seed=seed),
        muon=muon, run=ResilientConfig(steps=0, seed=LOOP_SEED),
        num_owners=1, mesh=remesh(devices) if chips > 1 else None)
    loop.pipe.close()
    loop.state = seeded_state(loop, arch, seed)
    log(f"[setup] {time.perf_counter() - t0:.1f} s: loop built, state "
        f"made on the device")
    rows = B // 2 if fault == "half_batch" else None
    feed = loop.pipe = Feed(gen, rows)
    tok = jax.ShapeDtypeStruct((rows or B, S), jnp.int32)
    unplant = plant(loop, fault)
    try:
        lowered = loop.step_fn.lower(loop.state, {"tokens": tok,
                                                  "labels": tok})
    finally:
        unplant()
    log(f"[setup] {time.perf_counter() - t0:.1f} s: step lowered")
    # A traced run reads each instruction's op_name from this executable:
    # key its compile on the metadata too, so that the names are this
    # checkout's own and not those that a checkout of equal operations left
    # in a shared cache.  Every other program keeps the untraced key.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", trace)
    try:
        compiled = loop.step_fn = lowered.compile()
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
    mem = compiled.memory_analysis()
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    log(f"[setup] {time.perf_counter() - t0:.1f} s: compiled step, "
        f"arguments {mem.argument_size_in_bytes} temp "
        f"{mem.temp_size_in_bytes} bytes per device")

    # -- check steps, through the window's own call and feed
    p0 = jax.device_get(flat(loop.state.params))

    def run_to(step: int) -> None:
        loop.rcfg.steps = step
        feed.seek(feed.step)
        loop.run()

    feed.prepare(0, n_check)
    run_to(1)
    grad_norms = first_grad_norms(loop.state.opt_state, ref_mod.ADAMW["b1"])
    run_to(n_check)
    p3 = jax.device_get(flat(loop.state.params))   # the change is taken
    # from these host copies once the window has closed
    prog = {"losses": losses_from_ema(
                [loop.report.losses[k] for k in range(n_check)]),
            "grad_norms": grad_norms}
    warm = loop.report.step_times[1:n_check] or loop.report.step_times
    step_s = statistics.median(warm)
    log(f"[setup] {time.perf_counter() - t0:.1f} s: {n_check} check "
        f"steps, step walls {loop.report.step_times}")

    # -- measured window
    out = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices())}
    values, ctx = {}, None
    if window:
        n = max(1, round(seconds / step_s))
        start = int(np.asarray(loop.state.step))
        feed.prepare(feed.step, feed.step + n)
        loop.rcfg.steps = start + n
        feed.seek(feed.step)
        counter = CompileCounter()
        tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        setup_s = time.perf_counter() - t0
        counter.on = True
        tw = time.perf_counter()
        loop.run()
        wall = time.perf_counter() - tw
        counter.on = False
        if trace:
            jax.profiler.stop_trace()
        times = loop.report.step_times[-n:]
        losses = [loop.report.losses[k] for k in range(start, start + n)]
        out["attempted"] = n
        out["failed"] = sum(not math.isfinite(x) for x in losses)
        log(f"[window] {n} steps in {wall:.4f} s, step walls "
            f"{[round(t, 4) for t in times]}, compiles {counter.count}")
        values = {"tokens_per_s": n * B * S / wall, "setup_s": setup_s}
        ctx = {"steps": n, "window_s": wall, "step_times": times,
               "config": conf, "traffic": traffic, "chips": chips,
               "mode": opt.get("mode", "owner"),
               "peaks": trace and peaks.peaks_for(devices[0].device_kind)}
    stats = [d.memory_stats() or {} for d in devices]
    device["memory_peak_bytes"] = max(
        [s.get("peak_bytes_in_use", 0) for s in stats] + [live])
    log(f"[memory] peak_bytes_in_use per device "
        f"{[s.get('peak_bytes_in_use') for s in stats]}, compiled "
        f"arguments + temp {live}")
    hlo = compiled.as_text() if trace and window else None
    del loop, lowered, compiled, feed
    gc.collect()

    if trace and window:
        summary = _reduce_trace(tdir, hlo, ctx)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = ctx["window_s"]
        out["breakdown"] = summary["breakdown"]
        for m in cell["per_layer"]:
            v = cell["readers"][m["name"]].read(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    elif window:
        for m in cell["end_to_end"]:
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}

    # -- the reference, once the program's state is gone
    tr0 = time.perf_counter()
    prog["deltas"] = {k: v - p0.pop(k) for k, v in p3.items()}
    del p3
    prog["delta_norms"] = {k: float(np.sqrt(np.sum(np.square(
        v, dtype=np.float64)))) for k, v in prog["deltas"].items()}
    if ref is None:
        ref = ref_mod.train_steps(
            conf, [gen.batch_at(k) for k in range(n_check)], seed,
            opt.get("mode", "owner"), devices=devices)
    readings = compare(prog, ref)
    log(f"[reference] {time.perf_counter() - tr0:.1f} s; program losses "
        f"{prog['losses']} reference {ref['losses']}")
    limits = cell["limits"]
    compared = [k for k in CHECKS if k in limits]
    out["correct"] = bool(compared) and all(readings[k] <= limits[k]
                                            for k in compared)
    out["device"] = device
    out["checks"] = {k: {"value": readings[k], "limit": limits[k]}
                     for k in compared}
    for k in CHECKS:
        if k not in limits:
            log(f"[reading, not compared] {k} {readings[k]!r}")
    for k in compared:
        log(f"check {k} {readings[k]!r} limit {limits[k]!r}")
    return out


def _reduce_trace(tdir: str, hlo: str, ctx: dict) -> dict:
    """Device time per class (``fwd_bwd``: ops of the step program whose
    op_name lies under ``jvp(`` or ``transpose(``; ``optimizer``: every
    other op of the step program), into ``ctx["trace"]``, and the step's
    ``{instruction: op_name}`` map, into ``ctx["op_names"]``."""
    import glob
    path = sorted(glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True))[-1]
    devices, hosts = tr.load(path)
    names = tr.op_names(hlo)

    def classify(name, stats):
        if name not in names:       # not an instruction of the step
            return None
        op = names[name]
        return "fwd_bwd" if ("jvp(" in op or "transpose(" in op) \
            else "optimizer"

    kinds = tr.collective_kinds(hlo)
    used = sorted(devices)[:ctx["chips"]]
    red = {d: tr.reduce_device(devices[d], classify, kinds) for d in used}
    ctx["trace"] = red
    ctx["op_names"] = names
    busy = statistics.mean(r["busy_ns"] for r in red.values()) * 1e-9
    ops = {}
    for r in red.values():
        for k, v in r["ops"].items():
            ops[k] = ops.get(k, 0.0) + v * 1e-9 / len(red)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    first = red[used[0]]
    evs = devices[used[0]]
    span = (min(e[1] for e in evs), max(e[2] for e in evs)) if evs \
        else (0.0, 1.0)
    log(f"[trace] {path}: devices {sorted(devices)}; per class s/step "
        + str({d: {c: v * 1e-9 / ctx['steps']
                   for c, v in r['class_ns'].items()}
               for d, r in red.items()}))
    return {"busy_s": busy, "breakdown": {
        "device_ops": [[f"{k} {names.get(k, '')[-80:]}".strip(), v]
                       for k, v in top],
        "idle_gaps": tr.label_gaps(first["gaps"], hosts, span)}}

