"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The ONLY entry point that forces 512 host devices — the env vars must be set
before jax initializes, hence the first lines.  It also pins the CPU
platform, so on a machine with a TPU neither it nor its ``--all`` children
(which inherit the environment) take the chip.  Each invocation handles
one cell (isolates compiler failures); ``--all`` re-invokes itself per cell
and aggregates the JSON results under ``results/dryrun/``.

Per cell it records: per-device HLO FLOPs / bytes-accessed (cost_analysis),
memory footprint (memory_analysis), and the collective mix parsed from the
compiled HLO (op counts + modeled wire bytes) — the inputs to §Roofline.
"""
import os

from repro.xla_flags import ensure_host_device_count

# Respect an existing device-count override (the test suite forces 8 via
# conftest.py before jax initializes); only the standalone CLI wants 512.
ensure_host_device_count(512)
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import re
import subprocess
import sys
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.dist import context as dctx
from repro.dist import partitioning as part
from repro.launch.mesh import make_production_mesh
from repro.models import model_lib as M
from repro.models.config import SHAPES, ModelConfig, ShapeSpec
from repro.models.layers import as_shapes
from repro.optim.adamw import AdamWConfig, apply_updates, init_state

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../../results/dryrun")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*(?P<ty>\([^)]*\)|[a-z0-9]+\[[^\]]*\]\S*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_GROUPS_1D_RE = re.compile(r"replica_groups=\[(\d+)\]<=\[")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _type_bytes(ty: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(ty):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# Link bandwidths for the modeled wire time: intra-pod ICI vs the much
# thinner pod-boundary (DCN) links the 2x16x16 pass exercises.
INTRA_POD_GBPS = 100.0
INTER_POD_GBPS = 25.0


def parse_collectives(hlo_text: str, pod_size: Optional[int] = None
                      ) -> Dict[str, Dict[str, float]]:
    """Per collective kind: instruction count + modeled per-device wire bytes
    (ring algorithms: AG/RS/A2A move size*(g-1)/g, AR moves 2x that,
    permute moves its full payload once).

    With ``pod_size`` set, replica groups larger than one pod additionally
    report ``cross_pod_bytes``: a ring over ``g`` contiguous devices
    spanning ``p = ceil(g / pod_size)`` pods crosses a pod boundary on
    ``p`` of its ``g`` hops, so that fraction of each device's wire bytes
    rides the inter-pod links (the bandwidth term
    :func:`collective_time_s` charges at ``INTER_POD_GBPS``).
    """
    out: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        nbytes = _type_bytes(m.group("ty"))
        g = 1
        gm = _GROUPS_RE.search(line)
        g1 = _GROUPS_1D_RE.search(line)
        gl = _GROUPS_LIST_RE.search(line)
        if gm:
            g = int(gm.group(2))
        elif g1:
            g = int(g1.group(1))
        elif gl:
            g = len(gl.group(1).split(","))
        if g <= 1:
            wire = 0.0
        elif op == "all-reduce":
            wire = 2.0 * nbytes * (g - 1) / g
        elif op == "all-gather":
            wire = nbytes * (g - 1) / g
        elif op == "reduce-scatter":
            wire = nbytes * (g - 1)  # result is the scattered shard
        elif op == "all-to-all":
            wire = nbytes * (g - 1) / g
        else:  # collective-permute
            wire = float(nbytes)
        cross = 0.0
        if pod_size and g > pod_size:
            spans = (g + pod_size - 1) // pod_size
            cross = wire * spans / g
        d = out.setdefault(op, {"count": 0, "wire_bytes": 0.0,
                                "cross_pod_bytes": 0.0})
        d["count"] += 1
        d["wire_bytes"] += wire
        d["cross_pod_bytes"] += cross
    return out


def collective_time_s(colls: Dict[str, Dict[str, float]], *,
                      intra_gbps: float = INTRA_POD_GBPS,
                      inter_gbps: float = INTER_POD_GBPS) -> float:
    """Modeled per-device wire time: intra-pod bytes at ICI bandwidth plus
    the pod-boundary fraction serialized on the inter-pod links."""
    t = 0.0
    for c in colls.values():
        cross = c.get("cross_pod_bytes", 0.0)
        t += ((c["wire_bytes"] - cross) / (intra_gbps * 1e9)
              + cross / (inter_gbps * 1e9))
    return t


# --------------------------------------------------------------------------
# per-cell lowering
# --------------------------------------------------------------------------

def _opt_cfg(cfg: ModelConfig) -> AdamWConfig:
    big = M.param_count(cfg) > 10e9
    return AdamWConfig(factored=big,
                       moment_dtype="bfloat16" if big else "float32")


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    sp: Dict[str, jax.ShapeDtypeStruct] = {}
    if shape.kind in ("train", "prefill"):
        sp["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        if shape.kind == "train":
            sp["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        if cfg.is_encoder_decoder:
            sp["frames"] = jax.ShapeDtypeStruct(
                (b, s // cfg.audio_frames_div, cfg.d_model), cfg.compute_dtype)
        if cfg.vision_dim:
            sp["patches"] = jax.ShapeDtypeStruct(
                (b, cfg.n_patches, cfg.vision_dim), cfg.compute_dtype)
    return sp


def lower_cell(cfg: ModelConfig, shape: ShapeSpec, mesh,
               unroll: bool = True, policy: str = "auto") -> Tuple:
    """Build (fn, example_args, in_shardings) for the cell kind.

    ``unroll=True`` is the cost-accounting lowering: XLA's cost analysis
    counts while-loop bodies once, so the roofline FLOP/collective numbers
    come from an unrolled stack.  ``unroll=False`` is the deployable scan
    lowering whose memory_analysis reflects real execution.
    """
    tokens = shape.global_batch * shape.seq_len
    if unroll:
        cfg = cfg.scaled(scan_layers=False, flash_attention=False,
                         loss_chunk=max(tokens // 8, min(8192, tokens)))
    if policy == "dp_only" and cfg.n_experts:
        raise ValueError("dp_only policy incompatible with expert parallelism")
    pspecs = M.param_specs(cfg)
    pshapes = as_shapes(pspecs)
    fsdp = M.param_count(cfg) > 3e9
    p_part = part.param_pspecs(pshapes, mesh, fsdp=fsdp,
                               tp=policy != "dp_only")
    p_shard = part.tree_shardings(p_part, mesh)

    if shape.kind == "train":
        ocfg = _opt_cfg(cfg)
        ostate = jax.eval_shape(lambda: init_state(ocfg, pshapes))
        o_part = part.opt_state_pspecs(pshapes, p_part, ostate, mesh)
        o_shard = part.tree_shardings(o_part, mesh)
        batch = input_specs(cfg, shape)
        b_shard = part.tree_shardings(part.batch_pspecs(batch, mesh), mesh)
        # Gradient-accumulation microbatching bounds activation memory in the
        # deployable (scan) lowering; the cost lowering keeps one full batch
        # (identical FLOPs, and scanning would hide them from cost analysis).
        n_micro = 1 if unroll else 4

        def train_step(params, opt_state, batch):
            if n_micro == 1:
                loss, grads = jax.value_and_grad(
                    lambda p: M.loss_fn(p, batch, cfg))(params)
            else:
                mb = jax.tree.map(
                    lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                        + x.shape[1:]), batch)

                def body(acc, one):
                    l, g = jax.value_and_grad(
                        lambda p: M.loss_fn(p, one, cfg))(params)
                    return (acc[0] + l,
                            jax.tree.map(jnp.add, acc[1], g)), None

                zero = (jnp.zeros((), jnp.float32),
                        jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype),
                                     params))
                (loss, grads), _ = jax.lax.scan(body, zero, mb)
                loss = loss / n_micro
                grads = jax.tree.map(lambda g: g / n_micro, grads)
            params, opt_state, metrics = apply_updates(
                ocfg, params, grads, opt_state)
            return params, opt_state, loss, metrics["grad_norm"]

        fn = jax.jit(train_step,
                     in_shardings=(p_shard, o_shard, b_shard),
                     out_shardings=(p_shard, o_shard, None, None),
                     donate_argnums=(0, 1))
        return fn, (pshapes, ostate, batch)

    if shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        b_shard = part.tree_shardings(part.batch_pspecs(batch, mesh), mesh)

        def prefill_step(params, batch):
            return M.prefill(params, batch, cfg)

        fn = jax.jit(prefill_step, in_shardings=(p_shard, b_shard))
        return fn, (pshapes, batch)

    # decode
    caches = M.cache_specs(cfg, shape.global_batch, shape.seq_len)
    c_part = part.cache_pspecs(caches, mesh)
    c_shard = part.tree_shardings(c_part, mesh)
    tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    tok_spec = jax.sharding.PartitionSpec(
        dp if shape.global_batch % dp_size == 0 else None, None)
    tok_shard = jax.sharding.NamedSharding(mesh, tok_spec)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def serve_step(params, token, pos, caches):
        return M.decode_step(params, token, pos, caches, cfg)

    fn = jax.jit(serve_step,
                 in_shardings=(p_shard, tok_shard, None, c_shard),
                 out_shardings=(None, None, c_shard),
                 donate_argnums=(3,))
    return fn, (pshapes, tok, pos, caches)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             policy: str = "auto", kv_dtype: str = "bf16",
             mem_only: bool = False) -> Dict:
    cfg = configs.get(arch)
    if kv_dtype != "bf16":
        cfg = cfg.scaled(kv_cache_dtype=kv_dtype)
    if os.environ.get("REPRO_MOE_GATHER"):
        cfg = cfg.scaled(moe_fsdp_gather=True)
    shape = next(s for s in SHAPES if s.name == shape_name)
    ok, why = cfg.runnable(shape)
    result: Dict = {"arch": arch, "shape": shape_name,
                    "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        result.update(status="skipped", reason=why)
        return result
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp_axes = ("pod", "data", "model") if policy == "dp_only" else None
    t0 = time.time()
    try:
        with dctx.use_mesh(mesh, dp_axes=dp_axes):
            # 1) deployable scan lowering: memory analysis.  The multi-pod
            # pass stops here — it proves the "pod" axis shards; the roofline
            # accounting (single-pod only, per the brief) needs pass 2.
            fn_s, args_s = lower_cell(cfg, shape, mesh, unroll=False,
                                      policy=policy)
            compiled_s = fn_s.lower(*args_s).compile()
            ma = compiled_s.memory_analysis()
            t1 = time.time()
            result.update(
                status="ok",
                scan_compile_s=round(t1 - t0, 1),
                mem=dict(
                    argument_bytes=int(ma.argument_size_in_bytes),
                    output_bytes=int(ma.output_size_in_bytes),
                    temp_bytes=int(ma.temp_size_in_bytes),
                    code_bytes=int(ma.generated_code_size_in_bytes),
                ),
                n_devices=mesh.size,
                params=M.param_count(cfg),
            )
            if multi_pod:
                # the multi-pod pass proves the "pod" axis shards AND prices
                # its boundary: groups spanning pods pay the inter-pod
                # bandwidth term on their cross-pod byte fraction
                pod_size = mesh.size // mesh.shape["pod"]
                colls = parse_collectives(compiled_s.as_text(),
                                          pod_size=pod_size)
                result.update(
                    collectives=colls,
                    wire_bytes_per_dev=sum(c["wire_bytes"]
                                           for c in colls.values()),
                    cross_pod_bytes_per_dev=sum(c["cross_pod_bytes"]
                                                for c in colls.values()),
                    wire_time_s=collective_time_s(colls),
                )
                return result
            if mem_only:
                return result
            # 2) unrolled lowering: FLOP / byte / collective accounting
            fn, args = lower_cell(cfg, shape, mesh, unroll=True,
                                  policy=policy)
            compiled = fn.lower(*args).compile()
            t2 = time.time()
            ca = compiled.cost_analysis() or {}
            colls = parse_collectives(compiled.as_text())
        result.update(
            compile_s=round(t2 - t1, 1),
            flops_per_dev=float(ca.get("flops", 0.0)),
            bytes_per_dev=float(ca.get("bytes accessed", 0.0)),
            collectives=colls,
            wire_bytes_per_dev=sum(c["wire_bytes"] for c in colls.values()),
        )
    except Exception as e:  # noqa: BLE001 — recorded, surfaced by --all
        result.update(status="error", error=f"{type(e).__name__}: {e}"[:2000])
    return result


def _result_path(out_dir, arch, shape, multi_pod):
    mesh = "multi" if multi_pod else "single"
    safe = arch.replace("/", "_")
    return os.path.join(out_dir, f"{safe}__{shape}__{mesh}.json")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--policy", default="auto",
                    choices=["auto", "dp_only"])
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["bf16", "int8"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--mem-only", action="store_true",
                    help="refresh only the scan-lowering memory analysis, "
                         "merging into an existing result JSON")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    if args.all:
        failures = 0
        for arch in configs.ARCH_NAMES:
            for shape in SHAPES:
                for mp in (False, True):
                    path = _result_path(args.out, arch, shape.name, mp)
                    if os.path.exists(path) and not args.force:
                        r = json.load(open(path))
                    else:
                        cmd = [sys.executable, "-m", "repro.launch.dryrun",
                               "--arch", arch, "--shape", shape.name,
                               "--out", args.out]
                        if mp:
                            cmd.append("--multi-pod")
                        try:
                            subprocess.run(cmd, check=False,
                                           timeout=args.timeout)
                        except subprocess.TimeoutExpired:
                            json.dump({"arch": arch, "shape": shape.name,
                                       "mesh": "2x16x16" if mp else "16x16",
                                       "status": "error",
                                       "error": "compile timeout"},
                                      open(path, "w"))
                        r = json.load(open(path)) if os.path.exists(path) \
                            else {"status": "error", "error": "no output"}
                    tag = r.get("status")
                    if tag == "error":
                        failures += 1
                    if tag == "ok":
                        info = (f"  flops/dev={r.get('flops_per_dev', 0):.3g} "
                                f"wire/dev={r.get('wire_bytes_per_dev', 0):.3g}B"
                                if not mp else
                                f"  temp/dev={r['mem']['temp_bytes']/1e9:.1f}GB")
                    else:
                        info = f"  ({r.get('reason', r.get('error', ''))[:70]})"
                    print(f"{arch:24s} {shape.name:12s} "
                          f"{'multi' if mp else 'single':6s} {tag}{info}",
                          flush=True)
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    r = run_cell(args.arch, args.shape, args.multi_pod,
                 policy=args.policy, kv_dtype=args.kv_dtype,
                 mem_only=args.mem_only)
    path = _result_path(args.out, args.arch, args.shape, args.multi_pod)
    if args.tag:
        path = path.replace(".json", f"__{args.tag}.json")
    if args.mem_only and os.path.exists(path):
        old = json.load(open(path))
        old.update({k: v for k, v in r.items()
                    if k in ("mem", "scan_compile_s", "status")})
        r = old
    with open(path, "w") as f:
        json.dump(r, f, indent=1)
    print(json.dumps({k: v for k, v in r.items() if k != "collectives"},
                     indent=1))
    return 0 if r["status"] != "error" else 1


if __name__ == "__main__":
    sys.exit(main())
