"""Model export for serving: AOT-compile and serialize the forward pass.

No reference analogue — the reference's only deployment story is running
``task=pred`` inside the training binary (reference: cxxnet_main.cpp:266).
TPU-native deployment wants the opposite: a self-contained artifact with
the weights baked in that any JAX runtime can execute without the
framework, the config dialect, or the checkpoint format. ``jax.export``
serializes the jitted forward as versioned StableHLO with strong
compatibility guarantees; the artifact runs via ``load_exported`` here,
or plain ``jax.export.deserialize`` anywhere else.

CLI: ``task = export_model`` with ``model_in`` and ``export_out``
(docs/tasks.md).
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional, Sequence

import numpy as np

from .obs import profile as _profile

MAGIC = "cxxnet_tpu.export.v1"


class MeshMismatchError(ValueError):
    """A mesh-carrying artifact cannot be realized on the local
    topology (wrong device count / axis shape): raised at LOAD time
    with the expected vs available topology named, instead of
    surfacing as an inscrutable XLA device-count failure at the first
    dispatch."""


def stage_host(*arrays, shardings=None):
    """Explicitly place host arrays on device before dispatching an
    exported program; device-resident arguments pass through untouched.

    Exported ``.call`` with a raw numpy argument pays an IMPLICIT
    host->device transfer per dispatch — invisible in the profile,
    disallowed under the armed shardcheck transfer sentinel
    (docs/analysis.md). This helper is the one sanctioned staging
    point the serving dispatch paths share.

    ``shardings`` (a per-argument sequence of ``NamedSharding``, from
    a mesh-carrying artifact's meta) makes staging MANDATORY and
    sharded: each host member is placed directly into its declared
    shards — an ``nr_devices > 1`` exported program cannot consume a
    host array at all, and staging anywhere else would pay an
    immediate reshard at dispatch. Entries may be None (argument
    already device-resident or deliberately left to jax).

    Seam discipline for the single-device path (the ``make_donating``
    pattern): with no shardcheck monitor enabled this is a single
    global read and the arrays pass through UNTOUCHED — jax's inline
    numpy conversion at dispatch is ~100us/call cheaper on the CPU
    backend than an explicit ``device_put``, and with no guard armed
    the implicit path is sanctioned. Monitored runs (the armed bench
    legs, the sentinel tests) stage explicitly and so prove the
    steady state clean."""
    if shardings is not None:
        import jax
        host_idx = [i for i, a in enumerate(arrays)
                    if isinstance(a, np.ndarray)
                    and i < len(shardings)
                    and shardings[i] is not None]
        if not host_idx:
            return arrays
        # ONE batched put for every host member, each into its
        # declared shards (per-array puts each cost a dispatch round
        # trip — the same lesson as trainer._put_batch)
        staged = jax.device_put([arrays[i] for i in host_idx],
                                [shardings[i] for i in host_idx])
        out = list(arrays)
        for i, s in zip(host_idx, staged):
            out[i] = s
        return tuple(out)
    from .analysis import shardcheck as _shardcheck
    if _shardcheck.active() is None:
        return arrays
    import jax
    # ONE batched put for every host member (per-array puts each cost
    # a dispatch round trip — the same lesson as trainer._put_batch);
    # device-resident members pass through untouched
    host_idx = [i for i, a in enumerate(arrays)
                if isinstance(a, np.ndarray)]
    if not host_idx:
        return arrays
    staged = jax.device_put(tuple(arrays[i] for i in host_idx))
    out = list(arrays)
    for i, s in zip(host_idx, staged):
        out[i] = s
    return tuple(out)


# ----------------------------------------------------------------------
# mesh-carrying artifacts: the mesh (axis names + shape + platform) and
# every program's per-argument PartitionSpecs are serialized into the
# .meta sidecar, validated at load against the local topology, and
# materialized into the NamedShardings the dispatch path stages with
# (docs/serving.md "sharded serving")

def _spec_to_json(spec) -> list:
    """PartitionSpec -> JSON: one entry per dim (axis name, list of
    axis names, or null for replicated)."""
    out = []
    for e in tuple(spec):
        if e is None:
            out.append(None)
        elif isinstance(e, (tuple, list)):
            out.append([str(a) for a in e])
        else:
            out.append(str(e))
    return out


def _spec_from_json(j):
    from jax.sharding import PartitionSpec
    return PartitionSpec(*[tuple(e) if isinstance(e, list) else e
                           for e in (j or [])])


def mesh_meta(mesh) -> dict:
    """The meta stanza a mesh-carrying artifact records: axis names +
    sizes in mesh order, device count, and the platform the programs
    were lowered for."""
    from .parallel import mesh_platform
    shape = [int(mesh.shape[a]) for a in mesh.axis_names]
    return {"axes": list(mesh.axis_names), "shape": shape,
            "devices": int(np.prod(shape)),
            "platform": mesh_platform(mesh)}


def mesh_data_parallel(mmeta) -> int:
    """The data-axis size of a meta mesh stanza (1 when absent)."""
    if not mmeta:
        return 1
    from .parallel import DATA_AXIS
    sizes = dict(zip(mmeta["axes"], mmeta["shape"]))
    return int(sizes.get(DATA_AXIS, 1))


def make_serving_mesh(data_parallel: int = 1, model_parallel: int = 1,
                      platform: Optional[str] = None):
    """Build an export/serving mesh over the first
    ``data_parallel * model_parallel`` local devices (the CLI's
    ``export_mesh`` knob and the bench legs go through here)."""
    from . import parallel
    n = int(data_parallel) * int(model_parallel)
    if n < 1:
        raise ValueError("mesh needs at least one device")
    devs = parallel.platform_devices(platform)
    if len(devs) < n:
        raise MeshMismatchError(
            "a %dx%d (data x model) mesh needs %d device(s); this "
            "process has %d %s device(s)"
            % (data_parallel, model_parallel, n, len(devs),
               devs[0].platform if devs else "?"))
    return parallel.make_mesh(devs[:n], model_parallel=model_parallel)


def resolve_mesh(mmeta):
    """Realize an artifact's recorded mesh on the LOCAL topology via
    ``parallel.make_mesh``, or raise :class:`MeshMismatchError` naming
    the expected vs available topology. Called at artifact LOAD — a
    topology that cannot carry the mesh must fail attributably before
    the first dispatch, not as an XLA device-count error inside it."""
    from . import parallel
    axes = [str(a) for a in mmeta["axes"]]
    shape = [int(x) for x in mmeta["shape"]]
    need = int(np.prod(shape))
    platform = mmeta.get("platform")
    devs = parallel.platform_devices(platform)
    if len(devs) < need:
        raise MeshMismatchError(
            "artifact carries a mesh %s over %d %s device(s); this "
            "process has %d %s device(s) — serve it on a topology "
            "that can realize the mesh, or re-export for this one "
            "(export_mesh=..., docs/serving.md)"
            % (dict(zip(axes, shape)), need, platform or "?",
               len(devs), devs[0].platform if devs else "?"))
    sizes = dict(zip(axes, shape))
    mesh = parallel.make_mesh(
        devs[:need],
        model_parallel=sizes.get(parallel.MODEL_AXIS, 1),
        seq_parallel=sizes.get(parallel.SEQ_AXIS, 1),
        pipeline_parallel=sizes.get(parallel.PIPE_AXIS, 1))
    got_axes = list(mesh.axis_names)
    got_shape = [int(mesh.shape[a]) for a in got_axes]
    if got_axes != axes or got_shape != shape:
        raise MeshMismatchError(
            "artifact mesh axes %s shape %s cannot be reconstructed "
            "by parallel.make_mesh on this topology (got axes %s "
            "shape %s)" % (axes, shape, got_axes, got_shape))
    return mesh


def _shardings(mesh, spec_jsons):
    """Materialize a meta's per-arg PartitionSpec list into the
    NamedShardings the staging/validation seams consume."""
    from jax.sharding import NamedSharding
    return tuple(None if j is None
                 else NamedSharding(mesh, _spec_from_json(j))
                 for j in spec_jsons)


def _shard_ladder(ladder: Sequence[int], dp: int) -> list:
    """Round every batch bucket UP to the next data-axis multiple
    (sorted, deduped): a mesh-carrying artifact's buckets must split
    evenly across the dp shards — an indivisible bucket would fall
    back to full replication (``parallel.input_sharding``'s counted
    fallback), which serving must never hit by construction."""
    dp = int(dp)
    return sorted({-(-int(b) // dp) * dp for b in ladder})


def auto_ladder(batch: int) -> list:
    """The default shape-bucket ladder for ``batch``: powers of two
    1, 2, 4, ... capped by ``batch``, with ``batch`` itself as the top
    rung (e.g. 24 -> [1, 2, 4, 8, 16, 24])."""
    batch = int(batch)
    if batch < 1:
        raise ValueError("batch must be >= 1, got %d" % batch)
    ladder, b = [], 1
    while b < batch:
        ladder.append(b)
        b *= 2
    ladder.append(batch)
    return ladder


def _norm_ladder(batch_ladder, batch_size) -> list:
    """Sorted unique bucket list; ``batch_size`` (when given) joins as
    a rung so the exported max batch honors it either way."""
    rungs = {int(b) for b in batch_ladder}
    if batch_size:
        rungs.add(int(batch_size))
    ladder = sorted(rungs)
    if not ladder:
        raise ValueError("batch_ladder must name at least one bucket")
    if ladder[0] < 1:
        raise ValueError("batch_ladder buckets must be >= 1, got %s"
                         % (ladder,))
    return ladder


def _xla_cost(jf, *args) -> Optional[dict]:
    """XLA's own cost estimate of one program: ``lower().
    cost_analysis()`` -> {"flops", "bytes"} or None. Recorded into
    artifact meta at export time as the CROSS-CHECK beside the
    analytic numbers, never as the MFU basis — XLA undercounts two
    shapes this tree verifiably hits (a ``lax.scan`` body counts once
    regardless of trip count, a Pallas kernel counts zero; see
    Trainer.step_cost_analysis) and some backends only report at the
    executable level, where compiling every exported program twice is
    not worth a cross-check. Pure best-effort: any failure is None."""
    try:
        ca = dict(jf.lower(*args).cost_analysis() or {})
    except Exception:
        return None
    out = {}
    if ca.get("flops") is not None:
        out["flops"] = float(ca["flops"])
    if ca.get("bytes accessed") is not None:
        out["bytes"] = float(ca["bytes accessed"])
    return out or None


def _params_bytes(params) -> float:
    """Total serialized-weight bytes of a params pytree — the
    weight-streaming term of the cost model's bytes lower bound."""
    import jax
    tot = 0
    for leaf in jax.tree.leaves(params):
        if hasattr(leaf, "size") and hasattr(leaf, "dtype"):
            tot += int(leaf.size) * np.dtype(leaf.dtype).itemsize
    return float(tot)


def profile_cost_table(meta: Optional[dict], dp: int = 1) -> dict:
    """obs/profile.py cost entries for a loaded artifact's meta:
    ``(site, phase, rung, bucket, width) -> (flops, bytes)``, keyed
    exactly the way the serving engines record profile events
    (docs/observability.md). Artifacts exported before the cost model
    carry no cost fields and yield an empty table — their events
    surface in the profiler's explicit ``uncosted`` list.

    ``dp`` is the engine's data-parallel degree: the continuous
    engine records ONE decode event per mesh shard (bucket = lanes
    per shard), so step costs register per-shard, divided by dp."""
    meta = meta or {}
    dp = max(int(dp), 1)
    table: dict = {}
    kind = meta.get("kind")
    if kind == "generate_step":
        T = int(meta.get("step_tokens", 1))
        kvds = meta.get("kv_dtypes") or ["native"]
        for pr in meta.get("programs") or []:
            f = pr.get("flops")
            if f is None:
                continue
            by = pr.get("bytes_streamed")
            if pr["kind"] == "prefill":
                # prefill programs are rung-agnostic (shared across
                # kv rungs) but the engine records them under the
                # rung it serves — register every rung's key
                for kvd in kvds:
                    table[("continuous", "prefill", kvd,
                           int(pr["rows"]), int(pr["width"]))] = (f, by)
            elif pr["kind"] == "tail_prefill":
                table[("continuous", "tail_prefill",
                       str(pr["kv_dtype"]), int(pr["rows"]),
                       int(pr["width"]))] = (f, by)
            elif pr["kind"] == "step":
                lps = int(pr["batch"]) // dp
                table[("continuous", "decode", str(pr["kv_dtype"]),
                       lps, T)] = (f / dp,
                                   None if by is None else by / dp)
    elif kind == "generate":
        per = int(meta.get("max_new", 1))
        for pr in meta.get("program_costs") or []:
            table[("engine", "decode_fixed", "fixed",
                   int(pr["bucket"]), per)] = (pr["flops"],
                                               pr.get("bytes_streamed"))
    else:
        for pr in meta.get("program_costs") or []:
            table[("engine", "forward", "fixed",
                   int(pr["bucket"]), 1)] = (pr["flops"],
                                             pr.get("bytes_streamed"))
    return table


def export_model(trainer, path: str,
                 batch_size: Optional[int] = None,
                 batch_ladder: Optional[Sequence[int]] = None,
                 platforms: Optional[Sequence[str]] = None,
                 mesh=None) -> None:
    """Serialize ``trainer``'s forward pass (weights baked in) to
    ``path`` (+ ``path.meta`` json with the io contract).

    The exported function maps a ``(batch, c, h, w)`` input to the
    output node's values (softmax probabilities for classifiers). The
    input contract mirrors what the trainer itself accepts: normalized
    float32 by default; when the trainer carries a raw-uint8 pipeline's
    deferred normalization (``on_device_norm``, net.input_norm set),
    the export takes raw uint8 pixels and bakes the ``(x-mean)*scale``
    in — the meta file records ``input_dtype`` either way.

    ``batch_ladder`` exports a SHAPE-BUCKET LADDER instead of one
    shape: each bucket's forward is serialized into the same artifact
    (blobs concatenated; meta records ``batch_ladder`` +
    ``ladder_blob_bytes``), so a serving engine can run a partial
    batch at the smallest bucket that fits instead of padding to the
    max — load-proportional compute (docs/serving.md). The meta's
    ``input_shape`` carries the max bucket, so single-shape readers
    keep working against the top rung.

    ``mesh`` exports a MESH-CARRYING artifact (docs/serving.md
    "sharded serving"): every bucket program is compiled under pjit
    with explicit ``in_shardings``/``out_shardings`` (batch over the
    ``data`` axis via ``parallel.input_sharding``), the mesh (axis
    names + shape + platform) and the per-arg PartitionSpecs are
    serialized into the meta, and the batch ladder is rounded UP to
    data-axis multiples so no bucket ever hits the replication
    fallback. At load the mesh is validated against the local
    topology (``resolve_mesh``); a data-parallel mesh then serves N×
    traffic from one engine. Weights are baked in as constants
    (replicated); tensor-parallel placement of internals follows
    GSPMD propagation from the declared boundary shardings.

    Multi-host: collective (all processes must call together to gather
    cross-process-sharded weights); only process 0 writes the files."""
    import jax
    from jax import export as jexport

    net = trainer.net
    if trainer.net_cfg.extra_data_num > 0:
        raise ValueError(
            "export_model does not support nets with extra data inputs "
            "(in_1.../attachtxt); the exported function takes the "
            "single primary input node")
    # gather (not device_get): zero=3 / cross-host-TP weights may span
    # processes — every process joins, process 0 writes
    params = jax.tree.map(
        lambda w: trainer._fetch_global(w) if w is not None else None,
        trainer.params)
    if jax.process_index() != 0:
        return
    if batch_ladder is not None:
        ladder = _norm_ladder(batch_ladder, batch_size)
    else:
        ladder = [int(batch_size or trainer.batch_size)]
    if mesh is not None:
        from .parallel import DATA_AXIS
        ladder = _shard_ladder(ladder, mesh.shape.get(DATA_AXIS, 1))
    bs = ladder[-1]
    item = tuple(net.node_shapes[0][1:])
    in_dtype = np.uint8 if net.input_norm is not None else np.float32

    def forward(data):
        # the artifact's mesh, not the training mesh the weights came
        # from: a mesh-free artifact is a one-device program
        with net.traced_on(mesh):
            values, _ = net.apply(params, data, train=False)
        return values[net.out_node]

    from .parallel import mesh_platform
    if platforms is None:
        platforms = [mesh_platform(mesh if mesh is not None
                                   else trainer.mesh)]
    # one rung exported, serialized, and written at a time: holding
    # every rung's weights-baked-in blob at once would multiply peak
    # host memory by the ladder length
    sizes = []
    in_specs = out_specs = None
    # serving cost model (obs/profile.py): analytic forward flops per
    # bucket — the train-side MFU basis (Network.analytic_model_flops)
    # scaled to the bucket's batch — plus the weight-stream bytes
    # lower bound, with XLA's own estimate as the recorded cross-check
    cfg_b = int(net.node_shapes[0][0]) or 1
    fwd_flops = net.analytic_model_flops(train=False)["fwd"]
    w_bytes = _params_bytes(params)
    item_bytes = float(np.prod(item)) * np.dtype(in_dtype).itemsize
    prog_costs = []
    with open(path, "wb") as f:
        for b in ladder:
            if mesh is not None:
                from .parallel import batch_sharding, input_sharding
                in_sh = input_sharding(mesh, (b,) + item)
                out_sh = batch_sharding(mesh)
                in_specs = [_spec_to_json(in_sh.spec)]
                out_specs = [_spec_to_json(out_sh.spec)]
                jf = jax.jit(forward, in_shardings=(in_sh,),
                             out_shardings=out_sh)
            else:
                jf = jax.jit(forward)
            sds = jax.ShapeDtypeStruct((b,) + item, in_dtype)
            blob = jexport.export(
                jf, platforms=list(platforms))(sds).serialize()
            f.write(blob)
            sizes.append(len(blob))
            cost = {"kind": "forward", "bucket": b,
                    "flops": fwd_flops * b / cfg_b,
                    "bytes_streamed": w_bytes + b * item_bytes}
            xc = _xla_cost(jf, sds)
            if xc:
                cost["xla_flops"] = xc.get("flops")
                cost["xla_bytes"] = xc.get("bytes")
            prog_costs.append(cost)
    out_shape = tuple(net.node_shapes[net.out_node])
    meta = {
        "magic": MAGIC,
        "input_shape": [bs] + list(item),
        "input_dtype": np.dtype(in_dtype).name,
        "output_shape": [bs] + list(out_shape[1:]),
        "platforms": list(platforms),
        "program_costs": prog_costs,
    }
    if mesh is not None:
        meta["mesh"] = mesh_meta(mesh)
        meta["in_shardings"] = in_specs
        meta["out_shardings"] = out_specs
    if len(ladder) > 1:
        meta["batch_ladder"] = ladder
        meta["ladder_blob_bytes"] = sizes
    with open(path + ".meta", "w") as f:
        json.dump(meta, f)


def export_generate(trainer, path: str, max_new: int = 32,
                    temperature: float = 0.0,
                    prompt_len: Optional[int] = None,
                    batch_size: Optional[int] = None,
                    batch_ladder: Optional[Sequence[int]] = None,
                    platforms: Optional[Sequence[str]] = None,
                    mesh=None) -> None:
    """Serialize the KV-cache DECODER (weights baked in) to ``path``.

    The exported function maps ``(tokens (B, S) int32, lens (B,)
    int32, key (2,) uint32)`` to the completed token matrix — the
    whole prefill + decode loop as one AOT program, no framework or
    checkpoint needed at serving time. ``prompt_len`` bounds the
    prompts the artifact accepts (sets the cache's static prompt
    region via ``generate.prompt_slots``; default ``seq_len -
    max_new``); the trainer's ``decode_layout``/``decode_kv`` knobs
    (including the int8 cache) resolve exactly as ``task=generate``
    would via ``Trainer._resolve_decode``. Requires the canonical LM
    graph (``generate.plan``). ``batch_ladder`` exports a shape-bucket
    ladder of decoders into one artifact (see ``export_model``) —
    every rung shares S/prompt_slots/max_new/temperature, only the
    slot count B varies, and layout/kv re-resolve per rung (kernel
    feasibility can depend on B). ``mesh`` exports a MESH-CARRYING
    decoder (see ``export_model``): slots shard over the ``data``
    axis (toks/lens in, token matrix out; the PRNG key replicates),
    the ladder rounds up to data-axis multiples, and the mesh + specs
    land in the meta. Multi-host: collective, process 0 writes, like
    ``export_model``."""
    import jax
    from jax import export as jexport

    from . import generate as G

    plan, why = G.plan_or_reason(trainer.net)
    if plan is None:
        raise ValueError(
            "export_generate needs the canonical LM graph "
            "(embed -> causal stack(s) -> head): " + why)
    net = trainer.net
    S = int(net.node_shapes[0][2])
    if batch_ladder is not None:
        # same contract as export_model: an explicit ladder caps the
        # artifact; trainer.batch_size only applies when no ladder and
        # no batch_size was given
        ladder = _norm_ladder(batch_ladder, batch_size)
    else:
        ladder = [int(batch_size or trainer.batch_size)]
    if mesh is not None:
        from .parallel import DATA_AXIS
        ladder = _shard_ladder(ladder, mesh.shape.get(DATA_AXIS, 1))
    B = ladder[-1]
    max_new = int(max_new)
    if max_new < 1:
        raise ValueError("max_new must be >= 1, got %d" % max_new)
    if prompt_len is None:
        prompt_len = max(1, S - max_new)
    prompt_len = int(prompt_len)
    if prompt_len < 1:
        raise ValueError("prompt_len must be >= 1")
    if prompt_len + max_new > S:
        raise ValueError(
            "prompt_len %d + max_new %d exceeds seq_len %d"
            % (prompt_len, max_new, S))
    P = G.prompt_slots(prompt_len, S)
    params = jax.tree.map(
        lambda w: trainer._fetch_global(w) if w is not None else None,
        trainer.params)
    if jax.process_index() != 0:
        return
    trainer._warn_moe_capacity(plan, "export_generate")
    from .parallel import mesh_platform
    platform = mesh_platform(mesh if mesh is not None
                             else trainer.mesh)
    if platforms is None:
        platforms = [platform]
    in_specs = out_specs = None
    if mesh is not None:
        from jax.sharding import NamedSharding
        from .parallel import DATA_AXIS
        data_sh = NamedSharding(mesh, _spec_from_json([DATA_AXIS]))
        repl_sh = NamedSharding(mesh, _spec_from_json([]))
        gen_in = (data_sh, data_sh, repl_sh)
        in_specs = [_spec_to_json(s.spec) for s in gen_in]
        out_specs = [_spec_to_json(data_sh.spec)]
    sizes, resolved, prog_costs = [], [], []
    with open(path, "wb") as f:
        for b in ladder:
            # layout/kv re-resolve per rung: kernel feasibility (slotk
            # grouping etc.) can depend on the slot count
            layout, kv = trainer._resolve_decode(plan, b, P, max_new)
            resolved.append((layout, kv))
            fn = G.build(net, plan, max_new, float(temperature), b, S,
                         P=P, layout=layout, platform=platform, kv=kv)

            def decode(toks, lens, key, _fn=fn):
                return _fn(params, toks, lens, key)

            if mesh is not None:
                jf = jax.jit(decode, in_shardings=gen_in,
                             out_shardings=data_sh)
            else:
                jf = jax.jit(decode)
            sds = (jax.ShapeDtypeStruct((b, S), np.int32),
                   jax.ShapeDtypeStruct((b,), np.int32),
                   jax.ShapeDtypeStruct((2,), np.uint32))
            # write rung by rung (see export_model): no whole-ladder
            # blob list resident at once
            blob = jexport.export(
                jf, platforms=list(platforms))(*sds).serialize()
            f.write(blob)
            sizes.append(len(blob))
            # serving cost model (obs/profile.py): analytic flops of
            # one whole prefill + max_new-step decode at this rung,
            # XLA's estimate as the recorded cross-check
            cost = dict(G.program_cost(net, plan, "decode_fixed",
                                       bucket=b, max_new=max_new,
                                       prompt_slots=P),
                        kind="decode_fixed", bucket=b)
            xc = _xla_cost(jf, *sds)
            if xc:
                cost["xla_flops"] = xc.get("flops")
                cost["xla_bytes"] = xc.get("bytes")
            cost["bytes_streamed"] = cost.pop("bytes")
            prog_costs.append(cost)
    meta = {
        "magic": MAGIC,
        "kind": "generate",
        "batch": B, "seq_len": S, "max_new": max_new,
        "max_prompt_len": prompt_len, "prompt_slots": P,
        "temperature": float(temperature),
        # the max rung's resolution is the headline contract; sub-max
        # rungs may legitimately resolve differently (feasibility
        # depends on B) and are listed per rung below
        "decode_layout": resolved[-1][0], "decode_kv": resolved[-1][1],
        "platforms": list(platforms),
        "program_costs": prog_costs,
    }
    if mesh is not None:
        meta["mesh"] = mesh_meta(mesh)
        meta["in_shardings"] = in_specs
        meta["out_shardings"] = out_specs
    if len(ladder) > 1:
        meta["batch_ladder"] = ladder
        meta["ladder_blob_bytes"] = sizes
        meta["ladder_decode_layout"] = [r[0] for r in resolved]
        meta["ladder_decode_kv"] = [r[1] for r in resolved]
    with open(path + ".meta", "w") as f:
        json.dump(meta, f)


def default_prefill_widths(max_prompt_len: int, seq_len: int) -> list:
    """The default prompt-width bucket ladder for a stepwise decoder:
    doubling 64-multiples (prompt_slots granularity) below the max
    prompt length, topped by the full prompt region P — so a short
    prompt runs a narrow prefill program instead of the artifact-wide
    one (the "long prompts must not tax short ones" half of the
    prefill/decode split)."""
    from . import generate as G
    P = G.prompt_slots(int(max_prompt_len), int(seq_len))
    widths, w = {P}, 64
    while w < max_prompt_len:
        widths.add(G.prompt_slots(w, seq_len))
        w *= 2
    return sorted(x for x in widths if x <= P)


def attend_kernel_name(paged_attend: str, kv_dtype: str) -> str:
    """Ledger/metrics label for a decode-step rung's attend kernel:
    ``gather-xla`` (the r10 materializing gather), ``fused-paged``
    (ops/paged_attend.py through the block table), ``fused-paged-q8``
    (same, int8 pages + scale planes)."""
    if paged_attend == "gather":
        return "gather-xla"
    return "fused-paged-q8" if kv_dtype == "int8" else "fused-paged"


def export_decode_step(trainer, path: str, max_new: int = 32,
                       temperature: float = 0.0,
                       prompt_len: Optional[int] = None,
                       batch_size: Optional[int] = None,
                       prefill_rows: Optional[Sequence[int]] = None,
                       prefill_widths: Optional[Sequence[int]] = None,
                       kv_block: int = 128,
                       pool_blocks: Optional[int] = None,
                       step_tokens: int = 4,
                       kv_dtypes: Optional[Sequence[str]] = None,
                       step_buckets: Optional[Sequence[int]] = None,
                       paged_attend: str = "fused",
                       tail_prefill: bool = True,
                       platforms: Optional[Sequence[str]] = None,
                       mesh=None) -> None:
    """Serialize the SPLIT-PHASE decoder for continuous batching:
    instead of ``export_generate``'s one monolithic prefill+decode
    loop, the artifact carries

    * PREFILL programs, one per (rows, width) bucket — a causal pass
      over a width-bucketed prompt window returning the prompt K/V
      (for the serving engine to scatter into its paged pool) and the
      first sampled token. Short prompts run narrow programs; a long
      prompt prefills in its own dispatch and never rides along with
      (or stalls) anyone else's.
    * DECODE-STEP programs over a paged KV pool — TYPED ARTIFACT
      RUNGS, one program per (``kv_dtype`` x slot bucket): each slot
      addresses its cache through a per-slot BLOCK TABLE into a shared
      pool of ``kv_block``-slot pages (the 128-multiple
      ``cache_slots`` granule from ops/decode_attend.py). Each call
      advances every slot by ``step_tokens`` tokens (multi-step
      scheduling: the per-call host dispatch amortizes over several
      tokens; a slot completing mid-call has its overshoot discarded);
      the serving engine rebinds slots between calls, which is what
      lets requests join and leave per call (Orca-style
      iteration-level scheduling), and dispatches each step at the
      smallest exported bucket holding the live rows, so partial
      occupancy runs a load-proportional program instead of the full
      slot count's.

    ``paged_attend`` picks the attend implementation baked into the
    step programs: ``fused`` (default) attends THROUGH the block table
    (ops/paged_attend.py — the Pallas paged kernel on TPU, the
    barrier-fenced merged-dot XLA form elsewhere; measured 1.35x over
    the gather step at the r12 bench shape); ``gather`` keeps the r10
    materializing gather as the measured baseline.

    ``kv_dtypes`` lists the cache-dtype rungs serialized into the
    artifact (default: the trainer's ``decode_kv`` knob, so
    ``decode_kv = int8`` routes to the int8 rung — the r10 loud
    rejection is gone now that the fused kernel exists): ``native``
    stores the compute dtype; ``int8`` stores int8 pages plus
    per-(page, head, slot) f32 absmax scale planes
    (``generate._quant8`` — prompt K/V is quantized on the way into
    the pool by ``scatter_prefill_kv``), halving the KV bytes the
    ~87%-streaming step moves and roughly doubling the sequences a
    pool byte budget holds. int8 requires ``paged_attend = "fused"``
    (the XLA gather attend on an int8 cache is a recorded perf
    negative). Prefill programs are rung-independent (they emit
    native K/V; quantization happens at scatter), so rungs share
    them.

    Pool geometry (recorded in the meta): logical per-slot cache =
    ``prompt_slots(prompt_len) + max_new`` attend slots, padded to the
    128-multiple ``cache_slots`` granule and cut into
    ``blocks_per_seq = cache_slots / kv_block`` pages;
    ``pool_blocks`` (default: full occupancy + 1) sizes the shared
    pool, with block 0 reserved as the trash page unbound slots write
    into.

    ``tail_prefill`` (default True) additionally serializes the
    INCREMENTAL prefill programs the cross-request prefix cache
    (serve/prefixcache.py) dispatches: one per (``kv_dtype`` x rows x
    tail-width bucket), each computing K/V for only the UNCACHED tail
    of a prompt while attending over the prefix pages already in the
    pool (``generate.build_tail_prefill``; pool buffers are read-only
    inputs, never donated — shared pages are copy-on-write). Only
    tail widths a cached prompt can actually need are exported
    (max tail = prompt_len - kv_block), and the whole family is
    skipped when P <= kv_block (no full page ever fits inside the
    prompt region, so nothing is shareable) — ``meta["ctx_blocks"]``
    and the ``tail_prefill`` program entries record what shipped.

    ``mesh`` exports a MESH-CARRYING split-phase decoder
    (docs/serving.md "sharded serving") — the typed-rung space grows
    one axis: kv_dtype x step bucket x MESH. Slots, step buckets,
    and prefill rows shard over the ``data`` axis (all rounded up to
    data-axis multiples), and the POOL's block dim shards over it
    too: the page space is cut into per-shard slices, each with its
    own trash page and free list (``pool_blocks_per_shard`` in the
    meta; serve/kvpool.py allocates per slice), so a row's block
    table stays inside the slice its dispatch shard owns and the
    step's page gather never leaves the shard. The mesh + per-arg
    PartitionSpecs serialize into the meta and are validated at load
    (``resolve_mesh``).

    Greedy outputs of the NATIVE rung are bitwise-identical to the
    monolithic ``export_generate`` artifact built from the same
    trainer (gather slices its pages to exactly the slot layout's
    attend width; the fused XLA form is bitwise-identical to gather
    by construction) — pinned by tests and by
    ``tools/decode_quality.py --paged``; the int8 rung is approximate
    (~1% relative attend error), gated by the same tool's
    ``--kv int8`` agreement threshold. A dp-MESH artifact's greedy
    outputs are bitwise-identical to a single-device artifact's at
    the matching PER-SHARD bucket shape (each shard runs exactly the
    per-shard program; pinned by tests/test_sharded_serving.py).
    Multi-host: collective, process 0 writes, like
    ``export_model``."""
    import jax
    from jax import export as jexport

    from . import generate as G

    plan, why = G.plan_or_reason(trainer.net)
    if plan is None:
        raise ValueError(
            "export_decode_step needs the canonical LM graph "
            "(embed -> causal stack(s) -> head): " + why)
    if paged_attend not in ("fused", "gather"):
        raise ValueError("paged_attend must be 'fused' or 'gather', "
                         "got %r" % (paged_attend,))
    if kv_dtypes is None:
        kv_dtypes = [getattr(trainer, "decode_kv", "native")]
    kv_dtypes = list(dict.fromkeys(kv_dtypes))   # ordered, unique
    for kvd in kv_dtypes:
        if kvd not in ("native", "int8"):
            raise ValueError("kv_dtypes entries must be 'native' or "
                             "'int8', got %r" % (kvd,))
    if "int8" in kv_dtypes and paged_attend != "fused":
        raise ValueError(
            "the int8 KV rung requires paged_attend='fused': the XLA "
            "gather attend on an int8 cache is a recorded perf "
            "negative (docs/performance.md)")
    net = trainer.net
    S = int(net.node_shapes[0][2])
    B = int(batch_size or trainer.batch_size)
    if B < 1:
        raise ValueError("batch_size must be >= 1")
    # mesh-carrying export: slots, step buckets, and prefill rows all
    # shard over the data axis, so each must split evenly across the
    # dp shards (buckets round UP — the ladder must never hit the
    # input_sharding replication fallback); the pool's page space is
    # cut into per-shard slices below
    dp = 1
    if mesh is not None:
        from .parallel import DATA_AXIS
        dp = int(mesh.shape.get(DATA_AXIS, 1))
        B = -(-B // dp) * dp
    max_new = int(max_new)
    if max_new < 1:
        raise ValueError("max_new must be >= 1, got %d" % max_new)
    if prompt_len is None:
        prompt_len = max(1, S - max_new)
    prompt_len = int(prompt_len)
    if prompt_len < 1:
        raise ValueError("prompt_len must be >= 1")
    if prompt_len + max_new > S:
        raise ValueError(
            "prompt_len %d + max_new %d exceeds seq_len %d"
            % (prompt_len, max_new, S))
    step_tokens = int(step_tokens)
    if step_tokens < 1:
        raise ValueError("step_tokens must be >= 1")
    step_tokens = min(step_tokens, max_new)
    P = G.prompt_slots(prompt_len, S)
    Sl = P + max_new                       # exact attend width
    from .ops.decode_attend import cache_slots
    # pool width on the 128-granule, with step_tokens - 1 slots of
    # headroom: a slot completing mid-call writes (discarded) K/V up
    # to step_tokens - 1 past its last real token, and those writes
    # must stay inside the slot's own pages
    Sp = cache_slots(P, max_new + step_tokens - 1)
    kv_block = int(kv_block)
    if kv_block < 1 or kv_block % 128 or Sp % kv_block:
        raise ValueError(
            "kv_block must be a 128-multiple dividing the %d-slot "
            "cache_slots granule, got %d" % (Sp, kv_block))
    nblk = Sp // kv_block
    if pool_blocks is None:
        # trash page + 4x occupancy: prefill is decoupled from lane
        # availability (serve/continuous.py prefills ahead into the
        # pool and parks rows on a ready queue until a slot frees —
        # that is what lets prefill dispatches batch at saturation),
        # and the ready backlog must be deep enough that holding a
        # prefill for a full rows bucket never starves a lane. Pages
        # are cheap; a too-small pool silently degrades the scheduler
        # to singleton prefills. On a mesh the geometry is computed
        # PER SLICE — each of the dp shards carries its own trash
        # page plus 4x its B/dp lanes' pages — then multiplied back
        # out to the global block dim the program shards
        pool_blocks = dp * (1 + 4 * (B // dp) * nblk)
    pool_blocks = int(pool_blocks)
    if pool_blocks % dp:
        raise ValueError(
            "pool_blocks (%d) must divide across the %d-way data "
            "axis: the pool's block dim is sharded over it, and each "
            "mesh slice carries its own trash page + free list"
            % (pool_blocks, dp))
    if pool_blocks // dp < 1 + nblk:
        raise ValueError(
            "pool_blocks must hold at least the trash page plus one "
            "sequence (%d blocks) per mesh slice, got %d%s"
            % (1 + nblk, pool_blocks,
               " over %d slices" % dp if dp > 1 else ""))
    if prefill_widths is None:
        widths = default_prefill_widths(prompt_len, S)
    else:
        widths = sorted({int(w) for w in prefill_widths})
        if not widths or widths[0] < 1 or widths[-1] > S:
            raise ValueError("prefill_widths must be in [1, %d], got %s"
                             % (S, widths))
        if widths[-1] < P:
            raise ValueError(
                "the widest prefill bucket (%d) must cover the prompt "
                "region P=%d" % (widths[-1], P))
    if prefill_rows is None:
        # mesh default: the usual 1..4-rows ladder per SHARD, scaled
        # by dp so every bucket splits evenly
        rows = auto_ladder(min(B, 4)) if dp == 1 \
            else [dp * r for r in auto_ladder(max(1, min(B // dp, 4)))]
    else:
        rows = sorted({int(r) for r in prefill_rows})
        if not rows or rows[0] < 1 or rows[-1] > B:
            raise ValueError("prefill_rows must be in [1, %d], got %s"
                             % (B, rows))
        if dp > 1:
            rows = [r for r in _shard_ladder(rows, dp) if r <= B]
    if step_buckets is None:
        buckets = [B]
    else:
        buckets = sorted({int(b) for b in step_buckets} | {B})
        if buckets[0] < 1 or buckets[-1] > B:
            raise ValueError(
                "step_buckets must be in [1, %d] (the slot count "
                "rides along as the top rung), got %s" % (B, buckets))
        if dp > 1:
            buckets = [b for b in _shard_ladder(buckets, dp) if b <= B]
    nh, d = G.uniform_heads_or_reason(net, plan)
    e_hidden = net.modules[plan["embed"]].param.num_hidden
    params = jax.tree.map(
        lambda w: trainer._fetch_global(w) if w is not None else None,
        trainer.params)
    if jax.process_index() != 0:
        return
    trainer._warn_moe_capacity(plan, "export_decode_step")
    import jax.numpy as jnp
    Ltot = sum(int(params[si]["wqkv"].shape[0])
               for si in plan["stacks"])
    pool_dt = jnp.dtype(net.compute_dtype)
    from .parallel import mesh_platform
    platform = mesh_platform(mesh if mesh is not None
                             else trainer.mesh)
    if platforms is None:
        platforms = [platform]
    SDS = jax.ShapeDtypeStruct
    programs = []
    rungs = []
    pool_shape = (pool_blocks, Ltot, nh, kv_block, d)
    scale_shape = pool_shape[:4]
    # mesh shardings (per program kind): rows/slots/tables over the
    # data axis, the pool's BLOCK dim over the data axis (each mesh
    # slice owns its own page slice — the per-shard pool), prefill
    # K/V outputs over their rows dim, the PRNG key replicated
    mesh_sh = None
    if mesh is not None:
        from jax.sharding import NamedSharding
        from .parallel import DATA_AXIS
        data_sh = NamedSharding(mesh, _spec_from_json([DATA_AXIS]))
        repl_sh = NamedSharding(mesh, _spec_from_json([]))
        rows2_sh = NamedSharding(mesh,
                                 _spec_from_json([None, DATA_AXIS]))
        pre_in = (data_sh, data_sh, repl_sh)
        pre_out = (data_sh, rows2_sh, rows2_sh)
        mesh_sh = {
            "pool": _spec_to_json(data_sh.spec),
            "prefill_in": [_spec_to_json(s.spec) for s in pre_in],
            "prefill_out": [_spec_to_json(s.spec) for s in pre_out],
            "step_in": {}, "step_out": {},
            "tail_in": {}, "tail_out": {},
        }
    # tail-prefill family (prefix cache): context = the prompt-region
    # pages; only tail widths a cached prompt can need (the cache
    # shares whole kv_block pages, so the max tail is
    # prompt_len - kv_block) — and nothing at all when no full page
    # fits inside the prompt region
    ctx_blocks = -(-P // kv_block)
    tail_widths = []
    if tail_prefill and P > kv_block:
        max_tail = max(prompt_len - kv_block, 1)
        cover = next((w for w in widths if w >= max_tail), widths[-1])
        tail_widths = [w for w in widths if w <= cover]
    # one program serialized and written at a time (see export_model):
    # no whole-artifact blob list resident at once
    with open(path, "wb") as f:
        for w in widths:
            for r in rows:
                fn = G.build_prefill(net, plan, float(temperature),
                                     r, w, platform, mesh)

                def pre(toks, lens, key, _fn=fn):
                    return _fn(params, toks, lens, key)

                jpre = jax.jit(pre, in_shardings=pre_in,
                               out_shardings=pre_out) \
                    if mesh is not None else jax.jit(pre)
                pre_sds = (SDS((r, w), np.int32), SDS((r,), np.int32),
                           SDS((2,), np.uint32))
                blob = jexport.export(
                    jpre, platforms=list(platforms))(
                        *pre_sds).serialize()
                f.write(blob)
                pc = G.program_cost(net, plan, "prefill", rows=r,
                                    width=w)
                entry = {"kind": "prefill", "rows": r,
                         "width": w, "bytes": len(blob),
                         "attend_impl": sorted({
                             G.prefill_attend_impl(
                                 net.modules[si], platform, w, e_hidden)
                             for si in plan["stacks"]}),
                         "flops": pc["flops"],
                         "bytes_streamed": pc["bytes"]}
                xc = _xla_cost(jpre, *pre_sds)
                if xc:
                    entry["xla_flops"] = xc.get("flops")
                    entry["xla_bytes"] = xc.get("bytes")
                programs.append(entry)
        for kvd in kv_dtypes:
            if kvd == "int8":
                pool_args = [SDS(pool_shape, np.int8),
                             SDS(pool_shape, np.int8),
                             SDS(scale_shape, np.float32),
                             SDS(scale_shape, np.float32)]
            else:
                pool_args = [SDS(pool_shape, pool_dt),
                             SDS(pool_shape, pool_dt)]
            # per-slot cache-stream bytes of this rung (K + V pages
            # plus the int8 scale planes) — the kv term of the cost
            # model's bytes lower bound AND the rung table below
            isz = 1 if kvd == "int8" else pool_dt.itemsize
            ssz = 4 if kvd == "int8" else 0
            slot_kv = 2.0 * Ltot * nh * Sp * (d * isz + ssz)
            donate = tuple(range(len(pool_args)))
            if mesh is not None:
                step_in = tuple([data_sh] * len(pool_args)) \
                    + (data_sh, data_sh, data_sh, data_sh, repl_sh)
                step_out = tuple([data_sh] * len(pool_args)) \
                    + (data_sh,)
                tail_in = tuple([data_sh] * len(pool_args)) \
                    + (data_sh, data_sh, data_sh, data_sh, repl_sh)
                mesh_sh["step_in"][kvd] = [
                    _spec_to_json(s.spec) for s in step_in]
                mesh_sh["step_out"][kvd] = [
                    _spec_to_json(s.spec) for s in step_out]
                mesh_sh["tail_in"][kvd] = [
                    _spec_to_json(s.spec) for s in tail_in]
                mesh_sh["tail_out"][kvd] = [
                    _spec_to_json(s.spec) for s in pre_out]
            for b in buckets:
                fn_step = G.build_step(
                    net, plan, float(temperature), b, P, Sl, kv_block,
                    platform, steps=step_tokens, kv=kvd,
                    attend=paged_attend, mesh=mesh)

                def stp(*a, _fn=fn_step):
                    return _fn(params, *a)

                # pool buffers (pages AND scale planes) donated: the
                # exported program carries the input-output aliasing,
                # so each step updates the pool in place instead of
                # copying it through twice per token
                if mesh is not None:
                    jstp = jax.jit(stp, donate_argnums=donate,
                                   in_shardings=step_in,
                                   out_shardings=step_out)
                else:
                    jstp = jax.jit(stp, donate_argnums=donate)
                stp_sds = tuple(pool_args) + (
                    SDS((b, nblk), np.int32), SDS((b,), np.int32),
                    SDS((b,), np.int32), SDS((b,), np.int32),
                    SDS((2,), np.uint32))
                blob = jexport.export(
                    jstp,
                    platforms=list(platforms))(*stp_sds).serialize()
                f.write(blob)
                pc = G.program_cost(
                    net, plan, "step", bucket=b,
                    step_tokens=step_tokens, attend_slots=Sl,
                    kv_bytes=b * step_tokens * slot_kv)
                entry = {"kind": "step", "kv_dtype": kvd,
                         "batch": b, "bytes": len(blob),
                         "flops": pc["flops"],
                         "bytes_streamed": pc["bytes"]}
                xc = _xla_cost(jstp, *stp_sds)
                if xc:
                    entry["xla_flops"] = xc.get("flops")
                    entry["xla_bytes"] = xc.get("bytes")
                programs.append(entry)
            for w in tail_widths:
                for r in rows:
                    fn = G.build_tail_prefill(
                        net, plan, float(temperature), r, w, kv_block,
                        ctx_blocks, platform, kv=kvd)

                    def tpre(*a, _fn=fn):
                        return _fn(params, *a)

                    # pool buffers are READ-ONLY inputs (no donation):
                    # a tail prefill must never write a shared page —
                    # the engine scatters the returned tail K/V into
                    # the row's OWN pages afterwards
                    jtp = jax.jit(tpre, in_shardings=tail_in,
                                  out_shardings=pre_out) \
                        if mesh is not None else jax.jit(tpre)
                    tp_sds = tuple(pool_args) + (
                        SDS((r, w), np.int32), SDS((r,), np.int32),
                        SDS((r,), np.int32),
                        SDS((r, nblk), np.int32),
                        SDS((2,), np.uint32))
                    blob = jexport.export(
                        jtp, platforms=list(platforms))(
                            *tp_sds).serialize()
                    f.write(blob)
                    Wc = ctx_blocks * kv_block
                    pc = G.program_cost(
                        net, plan, "tail_prefill", rows=r, width=w,
                        ctx_width=Wc,
                        kv_bytes=r * 2.0 * Ltot * nh * Wc
                        * (d * isz + ssz))
                    entry = {"kind": "tail_prefill",
                             "kv_dtype": kvd, "rows": r,
                             "width": w, "bytes": len(blob),
                             "flops": pc["flops"],
                             "bytes_streamed": pc["bytes"]}
                    xc = _xla_cost(jtp, *tp_sds)
                    if xc:
                        entry["xla_flops"] = xc.get("flops")
                        entry["xla_bytes"] = xc.get("bytes")
                    programs.append(entry)
            rungs.append({
                "kv_dtype": kvd,
                "attend_kernel": attend_kernel_name(paged_attend, kvd),
                # what build_step resolved the step programs' attend
                # to: ``pallas`` (the compiled paged kernel) or ``xla``
                # (the fused form's merged-dot fallback, the gather
                # attend); one answer for every bucket of a rung
                "attend_impl": fn_step.attend_impl,
                "pool_dtype": "int8" if kvd == "int8" else pool_dt.name,
                "scale_dtype": "float32" if kvd == "int8" else None,
                # bytes ONE slot's attend streams per decoded token
                # (K + V pages, plus the scale planes on int8) — the
                # per-rung traffic (``ExportedStepDecoder.rung()``)
                "kv_bytes_per_step": 2 * Ltot * nh * Sp * (d * isz
                                                           + ssz),
                # bytes one sequence's pages occupy in the pool — the
                # capacity side of the rung table (docs/serving.md)
                "kv_bytes_per_seq": 2 * nblk * Ltot * nh * kv_block
                * (d * isz + ssz),
            })
    meta = {
        "magic": MAGIC,
        "kind": "generate_step",
        "batch": B, "seq_len": S, "max_new": max_new,
        "max_prompt_len": prompt_len, "prompt_slots": P,
        "temperature": float(temperature),
        "attend_slots": Sl, "pool_slots": Sp,
        "step_tokens": step_tokens,
        "kv_block": kv_block, "blocks_per_seq": nblk,
        "pool_blocks": pool_blocks,
        "pool_dtype": pool_dt.name,
        "layers": Ltot, "nhead": nh, "head_dim": d,
        "prefill_rows": rows, "prefill_widths": widths,
        "decode_layout": "paged", "decode_kv": kv_dtypes[0],
        "paged_attend": paged_attend,
        "ctx_blocks": ctx_blocks,
        "tail_prefill_widths": tail_widths,
        "kv_dtypes": kv_dtypes, "step_buckets": buckets,
        "rungs": rungs,
        "programs": programs,
        "platforms": list(platforms),
    }
    if mesh is not None:
        meta["mesh"] = mesh_meta(mesh)
        meta["mesh_shardings"] = mesh_sh
        meta["pool_blocks_per_shard"] = pool_blocks // dp
    with open(path + ".meta", "w") as f:
        json.dump(meta, f)


class ExportedStepDecoder:
    """A deserialized ``export_decode_step`` artifact: the split-phase
    decoder the continuous-batching engine
    (serve/continuous.ContinuousDecodeEngine) schedules per token.

    * :meth:`prefill` runs the smallest (rows, width) bucket holding a
      request's prompt rows and returns ``(first_tokens, k, v)`` with
      the prompt K/V for the caller to scatter into the paged pool.
    * :meth:`step_call` hands out the donating step program of a
      (``kv_dtype``, slot bucket) RUNG; :meth:`step` is the legacy
      native-max-bucket shorthand (async either way: un-materialized
      device arrays; ``np.asarray`` the token matrix to block).
    * :meth:`generate` is the sequential reference driver — same
      contract as ``ExportedDecoder.__call__``, per-rung via ``kv`` —
      used by the parity tests and ``tools/decode_quality.py
      --paged``; serving goes through the engine instead."""

    def __init__(self, path: str, meta: dict):
        from jax import export as jexport
        self.meta = meta
        # mesh-carrying artifact: realize the recorded mesh on the
        # local topology NOW (resolve_mesh raises the attributed
        # MeshMismatchError when it cannot) and materialize the
        # per-program NamedShardings every dispatch stages with
        self.mesh = None
        self.dp = 1
        self._msh = {}
        mm = meta.get("mesh")
        if mm:
            self.mesh = resolve_mesh(mm)
            self.dp = mesh_data_parallel(mm)
            ms = meta.get("mesh_shardings") or {}
            if ms:
                self._msh = {
                    "pool": _shardings(self.mesh, [ms["pool"]])[0],
                    "prefill_in": _shardings(self.mesh,
                                             ms["prefill_in"]),
                    "step_in": {k: _shardings(self.mesh, v)
                                for k, v in ms["step_in"].items()},
                    "tail_in": {k: _shardings(self.mesh, v)
                                for k, v in ms["tail_in"].items()},
                }
        progs = meta.get("programs") or []
        with open(path, "rb") as f:
            blob = f.read()
        if sum(int(pr["bytes"]) for pr in progs) != len(blob):
            raise ValueError(
                "%s: generate_step meta does not match the blob "
                "(%d programs, %d bytes on disk)"
                % (path, len(progs), len(blob)))
        self._pre = {}
        self._pre_calls = {}      # (rows, width) -> staged wrapper
        self._step = {}           # (kv_dtype, bucket) -> exported
        self._step_calls = {}     # (kv_dtype, bucket) -> donating fn
        self._tail = {}           # (kv_dtype, rows, width) -> exported
        self._tail_calls = {}     # same key -> staged wrapper
        lo = 0
        for pr in progs:
            exp = jexport.deserialize(blob[lo:lo + int(pr["bytes"])])
            lo += int(pr["bytes"])
            if pr["kind"] == "prefill":
                self._pre[(int(pr["rows"]), int(pr["width"]))] = exp
            elif pr["kind"] == "tail_prefill":
                self._tail[(pr.get("kv_dtype", "native"),
                            int(pr["rows"]), int(pr["width"]))] = exp
            else:
                # pre-rung (r10) metas carry a bare {"kind": "step"}:
                # one native program at the full slot count
                kvd = pr.get("kv_dtype", "native")
                b = int(pr.get("batch", meta["batch"]))
                self._step[(kvd, b)] = exp
        if not self._step or not self._pre:
            raise ValueError(
                "%s: generate_step artifact needs at least one "
                "prefill program and one step program" % path)

    # -- artifact contract -------------------------------------------
    @property
    def batch(self) -> int:
        return int(self.meta["batch"])

    @property
    def seq_len(self) -> int:
        return int(self.meta["seq_len"])

    @property
    def max_prompt_len(self) -> int:
        return int(self.meta["max_prompt_len"])

    @property
    def max_new(self) -> int:
        return int(self.meta["max_new"])

    @property
    def prompt_slots(self) -> int:
        return int(self.meta["prompt_slots"])

    @property
    def step_tokens(self) -> int:
        return int(self.meta.get("step_tokens", 1))

    @property
    def kv_block(self) -> int:
        return int(self.meta["kv_block"])

    @property
    def blocks_per_seq(self) -> int:
        return int(self.meta["blocks_per_seq"])

    @property
    def pool_blocks(self) -> int:
        return int(self.meta["pool_blocks"])

    @property
    def pool_blocks_per_shard(self) -> int:
        """Pages one mesh slice owns (the whole pool on a
        single-device artifact): the per-shard page geometry the
        host allocator (serve/kvpool.BlockPool(shards=dp)) mirrors."""
        return int(self.meta.get("pool_blocks_per_shard",
                                 self.pool_blocks // self.dp))

    @property
    def buckets(self) -> list:
        return [self.batch]

    @property
    def kv_dtypes(self) -> list:
        """Exported cache-dtype rungs, artifact order (native first
        when both are present — the engine's 'auto' pick)."""
        kvs = self.meta.get("kv_dtypes")
        if kvs:
            return list(kvs)
        return sorted({kvd for kvd, _ in self._step})

    def step_buckets(self, kv: str = "native") -> list:
        """Exported slot buckets of the ``kv`` rung family."""
        out = sorted({b for kvd, b in self._step if kvd == kv})
        if not out:
            raise ValueError(
                "artifact has no %r step rung (exported: %s)"
                % (kv, self.kv_dtypes))
        return out

    def pick_step_bucket(self, n: int, kv: str = "native") -> int:
        """Smallest exported step bucket holding ``n`` live rows."""
        return _pick_bucket(self.step_buckets(kv), n)

    def profile_costs(self, dp: int = 1) -> dict:
        """Per-program analytic cost table for the program profiler
        (``obs/profile.py``), keyed by the (site, phase, rung, bucket,
        width) shapes the continuous engine records. ``dp`` divides
        the step flops across mesh shards (per-shard events)."""
        return profile_cost_table(self.meta, dp=dp)

    def rung(self, kv: str = "native") -> dict:
        """The rung's meta row (attend kernel, pool/scale dtypes,
        kv_bytes_per_step / kv_bytes_per_seq); synthesized for
        pre-rung (r10) artifacts."""
        for r in self.meta.get("rungs") or []:
            if r.get("kv_dtype") == kv:
                return dict(r)
        if kv != "native" or ("native", self.batch) not in self._step:
            raise ValueError(
                "artifact has no %r rung (exported: %s)"
                % (kv, self.kv_dtypes))
        import jax.numpy as jnp
        m = self.meta
        isz = jnp.dtype(m["pool_dtype"]).itemsize
        L, nh, d = int(m["layers"]), int(m["nhead"]), int(m["head_dim"])
        return {"kv_dtype": "native", "attend_kernel": "gather-xla",
                "pool_dtype": m["pool_dtype"], "scale_dtype": None,
                "kv_bytes_per_step": 2 * L * nh * int(m["pool_slots"])
                * d * isz,
                "kv_bytes_per_seq": 2 * L * nh * int(m["pool_slots"])
                * d * isz}

    @property
    def prefill_rows(self) -> list:
        return sorted({r for r, _ in self._pre})

    @property
    def prefill_widths(self) -> list:
        return sorted({w for _, w in self._pre})

    def pick_width(self, prompt_len: int) -> int:
        """Smallest exported prompt-width bucket holding the prompt."""
        for w in self.prefill_widths:
            if w >= prompt_len:
                return w
        raise ValueError(
            "prompt of %d tokens exceeds the widest prefill bucket %d"
            % (prompt_len, self.prefill_widths[-1]))

    def pick_rows(self, n: int) -> int:
        """Smallest exported prefill row bucket holding n rows whole;
        the max bucket when none does (the caller then chunks)."""
        return _pick_bucket(self.prefill_rows, n)

    # -- incremental (tail) prefill: the prefix-cache programs --------
    @property
    def ctx_blocks(self) -> int:
        """Prompt-region pages a tail prefill gathers as its attend
        context (``ceil(P / kv_block)``; meta-recorded)."""
        m = self.meta
        return int(m.get("ctx_blocks",
                         -(-int(m["prompt_slots"]) // self.kv_block)))

    def has_tail_prefill(self, kv: str = "native") -> bool:
        """Whether the artifact carries the ``kv`` rung's incremental
        prefill family — the prefix cache's hard prerequisite (pre-r14
        artifacts, and exports whose prompt region holds no full page,
        have none: the engine then serves with the cache off)."""
        return any(kvd == kv for kvd, _, _ in self._tail)

    def tail_widths(self, kv: str = "native") -> list:
        """Exported tail-width buckets of the ``kv`` rung family."""
        return sorted({w for kvd, _, w in self._tail if kvd == kv})

    def pick_tail_width(self, tail_len: int, kv: str = "native") -> int:
        """Smallest exported tail-width bucket holding ``tail_len``
        uncached tokens."""
        for w in self.tail_widths(kv):
            if w >= tail_len:
                return w
        raise ValueError(
            "tail of %d tokens exceeds the widest exported "
            "tail-prefill bucket (%s rung: %s)"
            % (tail_len, kv, self.tail_widths(kv)))

    def tail_call(self, kv: str, rows: int, width: int):
        """The (``kv``, ``rows``, ``width``) tail-prefill program:
        ``(pools..., toks (rows, width), clens (rows,), lens (rows,),
        bt (rows, nblk), key) -> (first (rows,), k (L, rows, nh,
        width, d), v)``. Pool buffers pass through READ-ONLY (no
        donation — shared prefix pages are copy-on-write, the caller
        scatters the tail K/V into the row's own pages); the per-call
        host arrays are staged through ``stage_host`` so the armed
        transfer sentinel sees a clean steady state."""
        key = (kv, int(rows), int(width))
        fn = self._tail_calls.get(key)
        if fn is None:
            from .analysis import shardcheck as _shardcheck
            exp = self._tail.get(key)
            if exp is None:
                raise ValueError(
                    "artifact has no (%s, rows=%d, width=%d) tail-"
                    "prefill program (exported: %s)"
                    % (kv, rows, width, sorted(self._tail)))
            site = "ExportedStepDecoder.tail[%s,r%d,w%d]" \
                % (kv, rows, width)
            in_sh = (self._msh.get("tail_in") or {}).get(kv)
            inner = _shardcheck.make_sharded(
                exp.call, in_shardings=in_sh, site=site, always=True)

            def fn(*a, _inner=inner, _sh=in_sh, _kv=kv,
                   _r=int(rows), _w=int(width)):
                pr = _profile.active()
                if pr is None:
                    return _inner(*stage_host(*a, shardings=_sh))
                # decoder-site profile event: submit-side wall of the
                # program call (async dispatch — NOT device time;
                # obs/profile.py module docstring)
                t0 = time.monotonic()
                out = _inner(*stage_host(*a, shardings=_sh))
                pr.record("decoder", "tail_prefill", _kv, _r, _w, -1,
                          (time.monotonic() - t0) * 1000.0)
                return out

            fn.__name__ = "staged[%s]" % site
            fn.__wrapped__ = inner
            self._tail_calls[key] = fn
        return fn

    def tail_prefill(self, pools, tokens, clens, lens, bt, key,
                     kv: str = "native"):
        """Run the smallest (rows, tail-width) bucket holding the
        uncached tails: ``tokens (n, >= max tail)`` carries each row's
        TAIL tokens left-aligned, ``clens`` the cached prefix lengths
        (kv_block multiples), ``lens`` the absolute prompt lengths,
        ``bt (n, blocks_per_seq)`` the full per-row block tables
        (shared prefix pages first). Pads rows with 1-token dummies on
        trash tables, trims the outputs back to ``n``. Returns
        ``(first (n,), k (L, n, nh, w, d), v)`` — the caller scatters
        k/v into the rows' OWN pages from ``starts=clens``."""
        n = int(tokens.shape[0])
        clens = np.asarray(clens, np.int32)
        lens = np.asarray(lens, np.int32)
        tl = int((lens - clens).max(initial=1))
        w = self.pick_tail_width(tl, kv)
        r = self.pick_rows(n)
        if r < n:
            raise ValueError(
                "tail prefill of %d rows exceeds the largest exported "
                "prefill bucket %d — chunk the request" % (n, r))
        toks = np.zeros((r, w), np.int32)
        toks[:n, :min(w, tokens.shape[1])] = \
            np.asarray(tokens, np.int32)[:, :w]
        cl = np.zeros((r,), np.int32)
        cl[:n] = clens
        ls = np.ones((r,), np.int32)
        ls[:n] = lens
        btm = np.zeros((r, self.blocks_per_seq), np.int32)
        btm[:n] = np.asarray(bt, np.int32)
        first, k, v = self.tail_call(kv, r, w)(
            *pools, toks, cl, ls, btm, key)
        return first[:n], k[:, :n], v[:, :n]

    def new_pool(self, kv: str = "native"):
        """Fresh zeroed pool buffers at the exported geometry
        (blocks, layers, nh, kv_block, head_dim): the ``(pool_k,
        pool_v)`` pair for the native rung, ``(pool_k, pool_v,
        scale_k, scale_v)`` — int8 pages plus f32 per-(page, head,
        slot) scale planes — for the int8 rung. The tuple's arity IS
        the rung's pool contract: every step/scatter call takes and
        returns exactly these buffers, donated."""
        import jax.numpy as jnp

        from .analysis import shardcheck as _shardcheck
        shape = (self.pool_blocks, int(self.meta["layers"]),
                 int(self.meta["nhead"]), self.kv_block,
                 int(self.meta["head_dim"]))
        # pool allocation is a deliberate device-buffer creation step
        # (the eager zeros/ones fills upload their scalar constants),
        # sanctioned under the armed transfer sentinel
        with _shardcheck.allow("pool-alloc"):
            if kv == "int8":
                # scale planes start at 1.0: a zero scale would be
                # safe (q=0 contributes nothing) but 1.0 keeps every
                # unwritten slot trivially readable — the slot-layout
                # convention
                bufs = (jnp.zeros(shape, jnp.int8),
                        jnp.zeros(shape, jnp.int8),
                        jnp.ones(shape[:4], jnp.float32),
                        jnp.ones(shape[:4], jnp.float32))
            else:
                dt = jnp.dtype(self.meta["pool_dtype"])
                bufs = (jnp.zeros(shape, dt), jnp.zeros(shape, dt))
            import jax
            if self.mesh is not None:
                # mesh pool: the block dim splits across the data
                # axis — each mesh slice owns its page slice, the
                # geometry the host allocator mirrors per shard
                return tuple(jax.device_put(a, self._msh["pool"])
                             for a in bufs)
            # COMMITTED to the device the zeros landed on: every
            # program's pool outputs are committed, and committedness
            # is part of jit's cache key — an uncommitted fresh pool
            # (engine start, pool-integrity reset after a fault) made
            # the donating scatter/step jits compile a second time in
            # steady state
            return tuple(jax.device_put(a, next(iter(a.devices())))
                         for a in bufs)

    def pre_call(self, rows: int, width: int):
        """The (``rows``, ``width``) prefill program behind the
        shardcheck seam with its staging baked in: host arrays are
        placed explicitly (into their declared shards on a
        mesh-carrying artifact — an ``nr_devices > 1`` program cannot
        consume host numpy at all), and the program registers for
        transfer/reshard attribution. Cached per bucket for the
        artifact's lifetime (``always=True``)."""
        key = (int(rows), int(width))
        fn = self._pre_calls.get(key)
        if fn is None:
            from .analysis import shardcheck as _shardcheck
            exp = self._pre.get(key)
            if exp is None:
                raise ValueError(
                    "artifact has no (rows=%d, width=%d) prefill "
                    "program (exported: %s)"
                    % (rows, width, sorted(self._pre)))
            site = "ExportedStepDecoder.prefill[r%d,w%d]" % key
            in_sh = self._msh.get("prefill_in")
            inner = _shardcheck.make_sharded(
                exp.call, in_shardings=in_sh, site=site, always=True)

            def fn(*a, _inner=inner, _sh=in_sh,
                   _r=int(rows), _w=int(width)):
                pr = _profile.active()
                if pr is None:
                    return _inner(*stage_host(*a, shardings=_sh))
                # decoder-site profile event (submit-side wall; the
                # "any" rung: prefill programs are shared across kv
                # rungs, so no single rung label applies)
                t0 = time.monotonic()
                out = _inner(*stage_host(*a, shardings=_sh))
                pr.record("decoder", "prefill", "any", _r, _w, -1,
                          (time.monotonic() - t0) * 1000.0)
                return out

            fn.__name__ = "staged[%s]" % site
            fn.__wrapped__ = inner
            self._pre_calls[key] = fn
        return fn

    def prefill(self, tokens: np.ndarray, lens: np.ndarray, key):
        """Run the smallest (rows, width) prefill bucket holding
        ``tokens (n, >= width)``: pads rows (1-token dummies), trims
        the outputs back to ``n``. Returns ``(first (n,) int32,
        k (L, n, nh, width, d), v (same))`` — K/V materialization is
        the caller's (it scatters them into its pool)."""
        n = int(tokens.shape[0])
        w = self.pick_width(int(lens.max(initial=1)))
        r = self.pick_rows(n)
        if r < n:
            raise ValueError(
                "prefill of %d rows exceeds the largest exported "
                "prefill bucket %d — chunk the request" % (n, r))
        toks = np.zeros((r, w), np.int32)
        toks[:n] = tokens[:, :w]
        ls = np.ones((r,), np.int32)
        ls[:n] = lens
        first, k, v = self.pre_call(r, w)(toks, ls, key)
        return first[:n], k[:, :n], v[:, :n]

    def step_call(self, kv: str = "native", bucket: int = None):
        """The donating step program of the (``kv``, ``bucket``) rung
        (default: the max bucket): a callable ``(pools..., bt, lens,
        stepv, last, key) -> (pools'..., next (bucket, step_tokens))``
        — async (no host sync), pool arity per :meth:`new_pool`.

        The pool arguments are DONATED: export serialization drops the
        program's input-output aliasing, so the call goes through an
        outer donating jit that restores it — without this every step
        round-trips the pool buffers through a copy (measured 10.5 ->
        3.9 ms/step at the bench shape). The caller must drop its old
        pool references and use the returned ones, even on failure
        (the donation-validator seam turns a violation into an
        immediate DonationError naming this site; docs/analysis.md)."""
        if bucket is None:
            bucket = self.step_buckets(kv)[-1]
        key = (kv, int(bucket))
        fn = self._step_calls.get(key)
        if fn is None:
            import jax

            from .analysis import jitcheck as _jitcheck
            from .analysis import shardcheck as _shardcheck
            exp = self._step.get(key)
            if exp is None:
                raise ValueError(
                    "artifact has no (%s, %d) step rung (exported: %s)"
                    % (kv, bucket, sorted(self._step)))
            npools = 4 if kv == "int8" else 2
            donate = tuple(range(npools))

            def exported_decode_step(*a, _call=exp.call):
                return _call(*a)

            # rung-qualified name: the recompile sentinel's
            # per-program counts stay attributable per rung
            exported_decode_step.__name__ = \
                "exported_decode_step_%s_b%d" % (kv, bucket)
            site = "ExportedStepDecoder.step[%s,b%d]" % (kv, bucket)
            # always=True: this wrapper is cached for the decoder's
            # lifetime, which may start before jitcheck.enable()
            # the outer jit re-adds only DONATION (export drops the
            # aliasing); its placements follow the committed sharded
            # inputs, which staging below guarantees match the
            # exported program's own declared shardings
            inner = _jitcheck.make_donating(
                jax.jit(exported_decode_step, donate_argnums=donate),
                argnums=donate, site=site, always=True)
            # sharding seam (docs/analysis.md): a mesh-carrying
            # artifact's materialized in_shardings validate every
            # call here (a mismatch is an attributed ReshardError
            # when armed); a single-device artifact just registers
            # the program for transfer-guard attribution
            in_sh = (self._msh.get("step_in") or {}).get(kv)
            inner = _shardcheck.make_sharded(
                inner, in_shardings=in_sh, site=site, always=True)

            stepw = int(self.meta.get("step_tokens", 1))

            def fn(*a, _inner=inner, _sh=in_sh, _kv=kv,
                   _b=int(bucket), _t=stepw):
                # per-call control arrays (block table, lens, step,
                # last, key) arrive as host numpy: stage them
                # explicitly — into their declared shards on a mesh —
                # so armed steady state pays no implicit transfer
                # (the pool buffers pass through untouched)
                pr = _profile.active()
                if pr is None:
                    return _inner(*stage_host(*a, shardings=_sh))
                # decoder-site profile event: submit-side wall only —
                # the step program is async (no host sync), so this
                # is dispatch cost, not device time; uncosted by
                # design (obs/profile.py docstring)
                t0 = time.monotonic()
                out = _inner(*stage_host(*a, shardings=_sh))
                pr.record("decoder", "decode", _kv, _b, _t, -1,
                          (time.monotonic() - t0) * 1000.0)
                return out

            fn.__name__ = "staged[%s]" % site
            fn.__wrapped__ = inner
            _jitcheck.forward_introspection(fn, inner)
            self._step_calls[key] = fn
        return fn

    def step(self, pool_k, pool_v, bt, lens, stepv, last, key):
        """Legacy shorthand for the native max-bucket rung's
        :meth:`step_call` — same donation contract."""
        return self.step_call("native")(pool_k, pool_v, bt, lens,
                                        stepv, last, key)

    def generate(self, tokens: np.ndarray, lens: np.ndarray,
                 seed: int = 0,
                 max_new: Optional[int] = None,
                 kv: str = "native") -> np.ndarray:
        """Sequential reference driver: decode ``tokens (n, S)`` /
        ``lens (n,)`` through prefill + per-token steps with a local
        block table, mirroring what the continuous engine does one
        request at a time. ``kv`` picks the artifact rung (the int8
        rung quantizes prompt K/V at scatter and new-token K/V in the
        step, exactly as serving would). Same output contract as
        ``ExportedDecoder.__call__``."""
        import jax
        m = self.meta
        S, B = self.seq_len, self.batch
        nblk = self.blocks_per_seq
        step_fn = self.step_call(kv)   # validates the rung up front
        toks = np.asarray(tokens, np.int32)
        lens = np.asarray(lens, np.int32)
        if toks.ndim != 2 or toks.shape[1] != S:
            raise ValueError(
                "tokens must be (n, %d), got %s" % (S, toks.shape))
        n = toks.shape[0]
        if n == 0:
            raise ValueError("tokens must carry at least one row")
        if lens.shape != (n,) or int(lens.min(initial=1)) < 1:
            raise ValueError(
                "lens must be (%d,) with every prompt >= 1 token" % n)
        if int(lens.max(initial=0)) > m["max_prompt_len"]:
            raise ValueError(
                "a prompt exceeds the exported max_prompt_len %d"
                % m["max_prompt_len"])
        n_new = self.max_new if max_new is None else int(max_new)
        if not 1 <= n_new <= self.max_new:
            raise ValueError("max_new must be in [1, %d], got %d"
                             % (self.max_new, n_new))
        from .analysis import shardcheck as _shardcheck
        with _shardcheck.allow("prng-seed"):
            base = jax.random.PRNGKey(int(seed))
        out = np.array(toks, copy=True)
        # per-shard geometry: each mesh slice owns B/dp lanes and its
        # own page slice (with its own trash page at the slice base);
        # chunk rows round-robin across shards so no slice overflows.
        # dp == 1 degenerates to the classic single-pool layout
        dp = self.dp
        Ls = B // dp
        bps = self.pool_blocks_per_shard
        rf = min(Ls, (bps - 1) // nblk)
        rows_fit = dp * rf
        for lo in range(0, n, rows_fit):
            t = toks[lo:lo + rows_fit]
            l = lens[lo:lo + rows_fit]
            mrows = t.shape[0]
            pools = self.new_pool(kv)
            # slot of chunk row r: shard r%dp, lane r//dp — every
            # row's pages come from its own shard's slice
            slot = [(r % dp) * Ls + r // dp for r in range(mrows)]
            bt = np.zeros((B, nblk), np.int32)
            for j in range(B):
                bt[j] = (j // Ls) * bps      # the slot's shard trash
            for r in range(mrows):
                sj, lane = r % dp, r // dp
                bt[slot[r]] = sj * bps + 1 + lane * nblk \
                    + np.arange(nblk)
            emitted = np.zeros((mrows, n_new), np.int32)
            # per-row prefill: row-independent, so grouping does not
            # change values — one row at a time keeps this driver
            # trivially correct for mixed prompt lengths
            for r in range(mrows):
                with _shardcheck.allow("prng-seed"):
                    key = np.asarray(jax.random.fold_in(base, lo + r),
                                     np.uint32)
                first, k, v = self.prefill(t[r:r + 1], l[r:r + 1], key)
                emitted[r, 0] = int(np.asarray(first)[0])
                pools = scatter_prefill_kv(
                    pools, k, v, [list(bt[slot[r]])], self.kv_block)
            blens = np.ones((B,), np.int32)
            for r in range(mrows):
                blens[slot[r]] = l[r]
            T = self.step_tokens
            i = 0
            while i < n_new - 1:
                stepv = np.full((B,), i, np.int32)
                last = np.zeros((B,), np.int32)
                for r in range(mrows):
                    last[slot[r]] = emitted[r, i]
                with _shardcheck.allow("prng-seed"):
                    key = np.asarray(
                        jax.random.fold_in(base, 1 << 20 | i),
                        np.uint32)
                out_t = step_fn(*pools, bt, blens, stepv, last, key)
                pools, nxt = out_t[:-1], out_t[-1]
                take = min(T, n_new - 1 - i)   # overshoot discarded
                nxt = np.asarray(nxt)
                for r in range(mrows):
                    emitted[r, i + 1:i + 1 + take] = \
                        nxt[slot[r], :take]
                i += take
            for r in range(mrows):
                out[lo + r, l[r]:l[r] + n_new] = emitted[r]
        return out


_SCATTER_CACHE: dict = {}


def scatter_prefill_kv(pools, k, v, block_tables, kv_block: int,
                       starts=None, valid=None):
    """Scatter prefill K/V ``(L, n, nh, W, d)`` into the paged pool at
    each row's block table (logical prompt slot ``j`` maps to page
    ``bt[j // kv_block]`` offset ``j % kv_block``). ``pools`` is the
    rung's buffer tuple from ``ExportedStepDecoder.new_pool``: the
    ``(pool_k, pool_v)`` pair for the native rung, or ``(pool_k,
    pool_v, scale_k, scale_v)`` for int8 — in which case the prompt
    K/V is QUANTIZED on the way in (``generate._quant8`` per (layer,
    row, head, slot), the same scheme the step program writes new
    tokens with). One jitted scatter with every pool array DONATED,
    so XLA updates the pool in place (the caller must drop its old
    references — the returned tuple replaces them); without donation
    every prefill would memcpy the whole pool through a copy.

    ``starts`` (per-row, kv_block multiples) makes the scatter
    OFFSET-CAPABLE — the prefix-cache tail prefill writes its K/V
    from logical slot ``starts[r]`` (i.e. from a start PAGE) instead
    of slot 0, so shared prefix pages below it are never touched
    (copy-on-write). ``valid`` (per-row tail lengths) routes the pad
    columns past each row's real tail to the trash page: an offset
    write's padding would otherwise land past the row's region. Both
    are HOST-side index arithmetic — the jitted program (and its
    compile cache key) is unchanged, which also keeps the recompile
    sentinel's warmup coverage intact."""
    import jax
    bt = np.asarray(block_tables, np.int32)          # (n, nb)
    n = bt.shape[0]
    W = int(k.shape[3])
    quant = len(pools) == 4
    # mesh pools: the block dim is sharded over the data axis — the
    # jit below follows the committed input shardings (no declaration
    # needed), the host index arrays stage replicated, and the cache
    # key carries the DATA-axis size (the pool's actual shard count,
    # not the mesh's total device count) so the program name stays
    # attributable per topology
    pool_mesh = getattr(getattr(pools[0], "sharding", None),
                        "mesh", None)
    if pool_mesh is not None:
        from .parallel import DATA_AXIS
        nshards = int(dict(pool_mesh.shape).get(DATA_AXIS, 1))
    else:
        nshards = 1
    key = (W, n, quant, tuple(pools[0].shape), str(pools[0].dtype),
           nshards)
    fn = _SCATTER_CACHE.get(key)
    if fn is None:
        from .analysis import jitcheck as _jitcheck

        if quant:
            from .generate import _quant8

            def _scat(pk, pv, ks, vs, kk, vv, b_idx, off):
                kq, ksn = _quant8(kk)
                vq, vsn = _quant8(vv)
                kt = kq.transpose(1, 3, 0, 2, 4)     # (n, W, L, nh, d)
                vt = vq.transpose(1, 3, 0, 2, 4)
                kst = ksn.transpose(1, 3, 0, 2)      # (n, W, L, nh)
                vst = vsn.transpose(1, 3, 0, 2)
                pk = pk.at[b_idx, :, :, off, :].set(kt)
                pv = pv.at[b_idx, :, :, off, :].set(vt)
                ks = ks.at[b_idx, :, :, off].set(kst)
                vs = vs.at[b_idx, :, :, off].set(vst)
                return pk, pv, ks, vs
            donate = (0, 1, 2, 3)
        else:
            def _scat(pk, pv, kk, vv, b_idx, off):
                kt = kk.transpose(1, 3, 0, 2, 4)     # (n, W, L, nh, d)
                vt = vv.transpose(1, 3, 0, 2, 4)
                pk = pk.at[b_idx, :, :, off, :].set(
                    kt.astype(pk.dtype))
                pv = pv.at[b_idx, :, :, off, :].set(
                    vt.astype(pv.dtype))
                return pk, pv
            donate = (0, 1)
        # per-shape name: the recompile sentinel's per-program counts
        # stay attributable (one compile per (width, rows) is warmup;
        # a second of the SAME name is a real recompile)
        _scat.__name__ = "scatter_prefill%s_w%d_n%d%s" % (
            "_q8" if quant else "", W, n,
            "_dp%d" % nshards if nshards > 1 else "")
        # always=True: the module-global cache outlives any one
        # jitcheck/shardcheck enable() window
        from .analysis import shardcheck as _shardcheck
        fn = _jitcheck.make_donating(
            jax.jit(_scat, donate_argnums=donate),
            argnums=donate, site="scatter_prefill_kv", always=True)
        fn = _shardcheck.make_sharded(fn, site="scatter_prefill_kv",
                                      always=True)
        _SCATTER_CACHE[key] = fn
    cols = np.arange(W)
    if starts is None:
        b_idx = bt[:, cols // kv_block].astype(np.int32)  # (n, W)
        off = np.ascontiguousarray(np.broadcast_to(
            cols % kv_block, (n, W))).astype(np.int32)
    else:
        logical = np.asarray(starts, np.int64)[:, None] \
            + cols[None, :]                               # (n, W)
        page = np.minimum(logical // kv_block, bt.shape[1] - 1)
        b_idx = np.take_along_axis(bt, page, axis=1).astype(np.int32)
        off = np.ascontiguousarray(logical % kv_block).astype(np.int32)
        if valid is not None:
            # pad columns past the row's real tail write to the trash
            # page (0): an offset scatter's padding would otherwise
            # land past the row's own region
            keep = cols[None, :] < np.asarray(valid,
                                              np.int64)[:, None]
            b_idx = np.where(keep, b_idx, 0).astype(np.int32)
    if pool_mesh is not None:
        from jax.sharding import NamedSharding
        repl = NamedSharding(pool_mesh, _spec_from_json([]))
        return fn(*pools, k, v,
                  *stage_host(b_idx, off, shardings=(repl, repl)))
    return fn(*pools, k, v, *stage_host(b_idx, off))


def _sharded_bucket_call(exps, in_shardings, calls, b: int, site: str):
    """The bucket program of a loaded artifact behind the shardcheck
    seam, built lazily and cached in ``calls`` (one wrapper per
    bucket for the artifact's lifetime, hence ``always=True``):
    registers the program for transfer/reshard attribution, and a
    mesh-carrying artifact's MATERIALIZED ``in_shardings`` validate
    every call (an arriving mismatch is an attributed ReshardError
    when armed — docs/analysis.md). Shared by ExportedModel and
    ExportedDecoder so the seam cannot drift between them."""
    fn = calls.get(b)
    if fn is None:
        from .analysis import shardcheck as _shardcheck
        fn = _shardcheck.make_sharded(
            exps[b].call, in_shardings=in_shardings,
            site=site, always=True)
        calls[b] = fn
    return fn


def _load_exps(path: str, meta: Optional[dict]):
    """Deserialize an artifact's program(s): a ``batch_ladder`` meta
    splits the blob into per-bucket programs (``{bucket: exported}``),
    a v1 single-shape artifact returns None (caller reads one blob)."""
    if not meta or not meta.get("batch_ladder"):
        return None
    from jax import export as jexport
    ladder = [int(b) for b in meta["batch_ladder"]]
    sizes = meta.get("ladder_blob_bytes")
    with open(path, "rb") as f:
        blob = f.read()
    if (not sizes or len(sizes) != len(ladder)
            or sum(int(s) for s in sizes) != len(blob)):
        raise ValueError(
            "%s: batch_ladder meta does not match the blob (%d buckets,"
            " ladder_blob_bytes %s vs %d bytes on disk)"
            % (path, len(ladder), sizes, len(blob)))
    exps, lo = {}, 0
    for b, n in zip(ladder, sizes):
        exps[b] = jexport.deserialize(blob[lo:lo + int(n)])
        lo += int(n)
    return exps


def _pick_bucket(buckets: Sequence[int], rows: int) -> int:
    """Smallest bucket that holds ``rows`` whole; the max bucket when
    none does (the caller then chunks)."""
    for b in buckets:
        if b >= rows:
            return b
    return buckets[-1]


class ExportedDecoder:
    """A deserialized ``export_generate`` artifact: ``__call__`` takes
    ``(tokens (n, S), lens (n,))`` int arrays (+ optional ``seed``)
    and returns the completed (n, S) token matrix. ``n`` need not equal
    the exported batch: short batches are padded with 1-token dummy
    rows up to the smallest exported bucket that fits (a ladder
    artifact carries several; a v1 artifact has exactly one) and the
    padding rows trimmed from the output; long batches run in
    max-bucket chunks. Row independence of the decode (per-sequence
    causal attention) keeps real rows byte-identical at temperature 0;
    at temperature > 0 the sampled stream depends on the bucket shape
    the rows land in, as it already depends on the batch they share a
    dispatch with."""

    def __init__(self, path: str, meta: dict):
        self._exps = _load_exps(path, meta)
        if self._exps is None:
            from jax import export as jexport
            with open(path, "rb") as f:
                self._exps = {int(meta["batch"]):
                              jexport.deserialize(f.read())}
        self.meta = meta
        self._calls: dict = {}
        # mesh-carrying artifact: realize the mesh locally (raises
        # MeshMismatchError at load when the topology cannot) and
        # materialize the per-arg shardings staging places into
        self.mesh = None
        self._in_sh = None
        mm = (meta or {}).get("mesh")
        if mm:
            self.mesh = resolve_mesh(mm)
            self._in_sh = _shardings(self.mesh, meta["in_shardings"])

    @property
    def batch(self) -> int:
        return int(self.meta["batch"])

    @property
    def seq_len(self) -> int:
        return int(self.meta["seq_len"])

    @property
    def buckets(self) -> list:
        return sorted(self._exps)

    def profile_costs(self) -> dict:
        """Per-program analytic cost table for the program profiler
        (``obs/profile.py``): decode_fixed per exported bucket."""
        return profile_cost_table(self.meta)

    def _bucket_call(self, b: int):
        # mesh-qualified site: the sentinel's per-program counts keep
        # a dp artifact's programs distinct from the single-device
        # baseline's when both serve in one process (the bench A/B)
        site = "ExportedDecoder.call[b%d]%s" % (
            b, "@dp%d" % mesh_data_parallel(self.meta.get("mesh"))
            if self.mesh is not None else "")
        return _sharded_bucket_call(self._exps, self._in_sh,
                                    self._calls, b, site)

    def call_exact(self, tokens: np.ndarray, lens: np.ndarray, key):
        """Run the bucket matching ``tokens.shape[0]`` exactly — no
        pad, no trim, and no host sync: returns the device array of
        JAX's async dispatch (``np.asarray`` it to block). The serving
        engine's pipelined dispatch lives on this. Host inputs are
        staged explicitly (``stage_host``) so armed steady state pays
        no implicit transfer — on a mesh artifact, directly into the
        declared shards."""
        b = tokens.shape[0]
        if b not in self._exps:
            raise ValueError(
                "no exported bucket of %d rows (ladder: %s)"
                % (b, self.buckets))
        return self._bucket_call(b)(
            *stage_host(tokens, lens, key, shardings=self._in_sh))

    def __call__(self, tokens: np.ndarray, lens: np.ndarray,
                 seed: int = 0) -> np.ndarray:
        import jax
        m = self.meta
        B, S = int(m["batch"]), int(m["seq_len"])
        buckets = self.buckets
        toks = np.asarray(tokens, np.int32)
        lens = np.asarray(lens, np.int32)
        if toks.ndim != 2 or toks.shape[1] != S:
            raise ValueError(
                "tokens must be (n, %d), got %s" % (S, toks.shape))
        n = toks.shape[0]
        if n == 0:
            raise ValueError("tokens must carry at least one row")
        if int(lens.max(initial=0)) > m["max_prompt_len"]:
            raise ValueError(
                "a prompt exceeds the exported max_prompt_len %d"
                % m["max_prompt_len"])
        if lens.shape != (n,) or int(lens.min(initial=1)) < 1:
            # same invariant Trainer.generate enforces: a 0-length row
            # would silently corrupt its output
            raise ValueError(
                "lens must be (%d,) with every prompt >= 1 token" % n)
        from .analysis import shardcheck as _shardcheck
        with _shardcheck.allow("prng-seed"):
            # distinct key per chunk past the first: reusing one key
            # would make rows i and B+i (same slot, same key) sample
            # identically at temperature>0; chunk 0 keeps the base key
            # so n <= B calls through the B-bucket match
            # tr.generate(seed) byte-exact (on a ladder artifact a
            # short call runs a smaller rung, whose sampled stream
            # differs at temperature>0 — see the class docstring).
            # Seed-material upload is sanctioned (allow window)
            base = jax.random.PRNGKey(seed)
            keys = [np.asarray(
                base if lo == 0 else jax.random.fold_in(base, lo // B),
                np.uint32) for lo in range(0, n, B)]
        outs = []
        for lo in range(0, n, B):
            t, l = toks[lo:lo + B], lens[lo:lo + B]
            b = _pick_bucket(buckets, t.shape[0])
            if t.shape[0] < b:
                pad = b - t.shape[0]
                t = np.concatenate([t, np.zeros((pad, S), np.int32)])
                l = np.concatenate([l, np.ones((pad,), np.int32)])
            outs.append(np.asarray(self._bucket_call(b)(
                *stage_host(t, l, keys[lo // B],
                            shardings=self._in_sh))))
        out = outs[0] if len(outs) == 1 else np.concatenate(outs)
        return out[:n]


class ExportedModel:
    """A deserialized export: ``__call__`` runs the forward, ``predict``
    adds the argmax-per-row convention of ``task=pred``.

    Each exported program accepts exactly its exported batch shape, but
    callers rarely arrive with it: ``__call__`` pads a short batch with
    zero rows up to the smallest exported bucket that fits (a
    ``batch_ladder`` artifact carries several; a v1 artifact has one)
    and trims the padding from the output, and runs a long batch in
    max-bucket chunks — row independence of the forward keeps real
    rows unchanged. The .meta sidecar supplies the contract; without
    it (bare blob) only the exact exported shape works — and a LADDER
    artifact's blob is a concatenation, so stripped of its sidecar it
    degrades to the first (smallest) rung: keep the sidecar next to
    ladder artifacts."""

    def __init__(self, path: str, meta: Optional[dict] = None):
        self.meta = meta
        if meta is None:
            meta_path = path + ".meta"
            if os.path.exists(meta_path):
                with open(meta_path) as f:
                    self.meta = json.load(f)
                # reject a foreign sidecar before deserializing the
                # blob: flatbuffers errors on garbage are inscrutable
                if self.meta.get("magic") != MAGIC:
                    raise ValueError("%s: not a cxxnet_tpu export"
                                     % path)
        self._exps = _load_exps(path, self.meta)
        if self._exps is None:
            from jax import export as jexport
            with open(path, "rb") as f:
                exp = jexport.deserialize(f.read())
            shape = (self.meta or {}).get("input_shape")
            # a meta-less bare blob has no batch contract: leave the
            # bucket map empty and keep the single program (its own
            # shape check is the only contract)
            self._exps = {int(shape[0]): exp} if shape else {}
            self._exp = exp
        else:
            self._exp = self._exps[max(self._exps)]
        self._calls: dict = {}
        # mesh-carrying artifact (see ExportedDecoder): topology
        # validated at load, shardings materialized for staging
        self.mesh = None
        self._in_sh = None
        mm = (self.meta or {}).get("mesh")
        if mm:
            self.mesh = resolve_mesh(mm)
            self._in_sh = _shardings(self.mesh,
                                     self.meta["in_shardings"])

    def _bucket_call(self, b: int):
        # mesh-qualified site (see ExportedDecoder._bucket_call)
        site = "ExportedModel.call[b%d]%s" % (
            b, "@dp%d" % mesh_data_parallel(self.meta.get("mesh"))
            if self.mesh is not None else "")
        return _sharded_bucket_call(self._exps, self._in_sh,
                                    self._calls, b, site)

    @property
    def batch(self) -> Optional[int]:
        shape = (self.meta or {}).get("input_shape")
        return int(shape[0]) if shape else None

    @property
    def buckets(self) -> Optional[list]:
        """Sorted exported batch sizes; None for a meta-less blob."""
        return sorted(self._exps) if self._exps else None

    def profile_costs(self) -> dict:
        """Per-program analytic cost table for the program profiler
        (``obs/profile.py``): forward per exported bucket."""
        return profile_cost_table(self.meta)

    def call_exact(self, data: np.ndarray):
        """Run the bucket matching ``data.shape[0]`` exactly — no pad,
        no trim, no host sync: returns JAX's async-dispatch device
        array (``np.asarray`` it to block). The serving engine's
        pipelined dispatch lives on this. Host inputs are staged
        explicitly (``stage_host``) so armed steady state pays no
        implicit transfer."""
        if not self._exps:    # bare blob: the one program shape-checks
            return self._exp.call(*stage_host(data))
        b = data.shape[0]
        if b not in self._exps:
            raise ValueError(
                "no exported bucket of %d rows (ladder: %s)"
                % (b, sorted(self._exps)))
        return self._bucket_call(b)(
            *stage_host(data, shardings=self._in_sh))

    def __call__(self, data: np.ndarray) -> np.ndarray:
        dt = np.dtype((self.meta or {}).get("input_dtype", "float32"))
        arr = np.asarray(data, dt)
        shape = (self.meta or {}).get("input_shape")
        if shape is None or arr.shape == tuple(shape):
            if self._exps:          # the max bucket, behind the seam
                return np.asarray(self._bucket_call(max(self._exps))(
                    *stage_host(arr, shardings=self._in_sh)))
            return np.asarray(self._exp.call(*stage_host(arr)))
        B = int(shape[0])
        buckets = sorted(self._exps)
        item = tuple(shape[1:])
        if arr.ndim != 1 + len(item) or tuple(arr.shape[1:]) != item:
            raise ValueError(
                "data must be (n, %s), got %s"
                % (", ".join(map(str, item)), arr.shape))
        n = arr.shape[0]
        if n == 0:
            raise ValueError("data must carry at least one row")
        outs = []
        for lo in range(0, n, B):
            chunk = arr[lo:lo + B]
            b = _pick_bucket(buckets, chunk.shape[0])
            if chunk.shape[0] < b:
                pad = np.zeros((b - chunk.shape[0],) + item, dt)
                chunk = np.concatenate([chunk, pad])
            outs.append(np.asarray(self._bucket_call(b)(
                *stage_host(chunk, shardings=self._in_sh))))
        out = outs[0] if len(outs) == 1 else np.concatenate(outs)
        return out[:n]

    def predict(self, data: np.ndarray) -> np.ndarray:
        out = self(data)
        out = out.reshape(out.shape[0], -1)
        if out.shape[1] == 1:   # regression output: raw values
            return out[:, 0]
        return np.argmax(out, axis=1).astype(np.float32)


def load_exported(path: str):
    """Load an export artifact; dispatches on the meta ``kind``
    (forward -> ``ExportedModel``, generate -> ``ExportedDecoder``)."""
    meta_path = path + ".meta"
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
        if meta.get("magic") != MAGIC:
            raise ValueError("%s: not a cxxnet_tpu export" % path)
        if meta.get("kind") == "generate_step":
            return ExportedStepDecoder(path, meta)
        if meta.get("kind") == "generate":
            return ExportedDecoder(path, meta)
        return ExportedModel(path, meta)
    return ExportedModel(path)
