# The FIRST two lines — before ANY other import — force 512 placeholder
# devices so jax.make_mesh can build the production mesh (jax locks the
# device count at first init).  Never set this globally: smoke tests and
# benches must see the single real CPU device.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse    # noqa: E402
import json        # noqa: E402
import re          # noqa: E402
import time        # noqa: E402
import traceback   # noqa: E402
from pathlib import Path  # noqa: E402

import jax         # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ..configs import ALL_ARCHS, get_config          # noqa: E402
from ..configs.base import SHAPES                     # noqa: E402
from ..models import build_model                      # noqa: E402
from ..parallel.sharding import axis_rules, param_sharding, resolve  # noqa: E402
from ..train.optimizer import make_optimizer          # noqa: E402
from .mesh import make_production_mesh                # noqa: E402

# ------------------------------------------------------------ HLO parsing

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes_from_hlo(hlo_text: str):
    """Sum output-shape bytes of every collective op in the (per-device)
    HLO module.  Returns {kind: {"bytes": int, "count": int}}."""
    out = {k: {"bytes": 0, "count": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"%?[\w.\-]+ = (.+?) (\w[\w-]*)\(", stripped)
        if not m:
            continue
        result_type, opname = m.group(1), m.group(2)
        # normalize: all-reduce-start / all-gather-done etc.
        base = None
        for k in _COLLECTIVES:
            if opname == k or opname.startswith(k + "-start"):
                base = k
                break
        if base is None:
            continue
        out[base]["bytes"] += _shape_bytes(result_type)
        out[base]["count"] += 1
    return out


# ------------------------------------------------------------- step fns


def make_train_step(model, optimizer):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        return new_params, new_opt, loss
    return train_step


def make_prefill_step(model):
    def prefill_step(params, batch):
        logits, _ = model.logits_fn(params, batch)
        return logits
    return prefill_step


def make_serve_step(model):
    def serve_step(params, cache, batch):
        return model.decode_step(params, cache, batch)
    return serve_step


# ------------------------------------------------------------- dry run


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                preset: str = "baseline", verbose: bool = True,
                scan_layers: bool = False, overrides=None,
                donate: bool = False):
    """Lower + compile one (arch × shape × mesh) cell; return the record.

    Layers are UNROLLED by default (scan_layers=False): XLA's HLO cost
    analysis does not multiply while-loop bodies by their trip count, so the
    roofline terms are only trustworthy on an unrolled module."""
    cfg = get_config(arch).replace(scan_layers=scan_layers,
                                   **(overrides or {}))
    shape = cfg.shapes().get(shape_name)
    if shape is None:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "skipped": True,
                "reason": ("long_500k needs sub-quadratic attention"
                           if shape_name == "long_500k" else "not applicable")}
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()

    with axis_rules(mesh, preset=preset):
        param_shapes, param_specs = model.abstract_params()
        p_shard = param_sharding(param_specs, mesh, shapes=param_shapes)
        batch_shapes = model.input_specs(shape)
        batch_axes = model.input_axes(shape)
        b_shard = {
            k: jax.NamedSharding(mesh, resolve(batch_axes[k],
                                               batch_shapes[k].shape))
            for k in batch_shapes
        }

        if shape.kind == "train":
            optimizer = make_optimizer(cfg.optimizer)
            opt_shapes, opt_specs = optimizer.abstract_state(
                param_shapes, param_specs)
            o_shard = param_sharding(opt_specs, mesh, shapes=opt_shapes)
            fn = make_train_step(model, optimizer)
            jitted = jax.jit(
                fn,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1) if donate else (),
            )
            lowered = jitted.lower(param_shapes, opt_shapes, batch_shapes)
        elif shape.kind == "prefill":
            fn = make_prefill_step(model)
            full_seq = (shape.seq_len if cfg.family != "vlm"
                        else shape.seq_len)
            logits_spec = jax.NamedSharding(
                mesh, resolve(("batch", "seq", "act_vocab"),
                              shape=(shape.global_batch, full_seq,
                                     cfg.vocab_size)))
            jitted = jax.jit(fn, in_shardings=(p_shard, b_shard),
                             out_shardings=logits_spec)
            lowered = jitted.lower(param_shapes, batch_shapes)
        else:  # decode
            cache_shapes, cache_specs = model.init_cache(
                shape.global_batch, shape.seq_len)
            c_shard = param_sharding(cache_specs, mesh, shapes=cache_shapes)
            fn = make_serve_step(model)
            logits_spec = jax.NamedSharding(
                mesh, resolve(("batch", "act_vocab"),
                              shape=(shape.global_batch, cfg.vocab_size)))
            jitted = jax.jit(fn, in_shardings=(p_shard, c_shard, b_shard),
                             out_shardings=(logits_spec, c_shard),
                             donate_argnums=(1,) if donate else ())
            lowered = jitted.lower(param_shapes, cache_shapes, batch_shapes)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_info = {"error": str(e)}

    hlo = compiled.as_text()
    colls = collective_bytes_from_hlo(hlo)

    record = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "multi_pod": multi_pod,
        "preset": preset,
        # scan-mode records prove compile-fit only (FLOPs undercounted —
        # the roofline table marks them)
        "scan_layers": scan_layers,
        "n_chips": int(n_chips),
        "mesh": dict(mesh.shape),
        "flops_per_device": float(cost.get("flops", -1)),
        "bytes_accessed_per_device": float(cost.get("bytes accessed", -1)),
        "memory": mem_info,
        "collectives": colls,
        "collective_bytes_per_device": sum(
            v["bytes"] for v in colls.values()),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "optimizer": cfg.optimizer if shape.kind == "train" else None,
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × "
              f"{'2x16x16' if multi_pod else '16x16'} preset={preset}: "
              f"compile {t_compile:.1f}s, "
              f"flops/dev={record['flops_per_device']:.3e}, "
              f"coll/dev={record['collective_bytes_per_device']:.3e}B")
        print("  memory_analysis:", mem_info)
        print("  cost_analysis keys:", sorted(cost)[:12])
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id")
    ap.add_argument("--shape", default=None, help="shape name")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch × shape) cell")
    ap.add_argument("--preset", default="baseline")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (e.g. remat=none)")
    ap.add_argument("--donate", action="store_true",
                    help="donate state buffers (in-place cache/param update)")
    ap.add_argument("--scan", action="store_true",
                    help="scan-over-layers (fast compile; use for pure "
                         "compile-fit verification — FLOPs undercounted)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("true", "True"):
            v = True
        if v in ("false", "False"):
            v = False
        overrides[k] = v

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    # small → large so the sweep yields results early
    SIZE_ORDER = [
        "whisper-base", "tinyllama-1.1b", "zamba2-1.2b", "mamba2-1.3b",
        "olmoe-1b-7b", "qwen3-8b", "qwen3-32b", "deepseek-v2-236b",
        "qwen2-vl-72b", "llama3-405b",
    ]
    cells = []
    archs = SIZE_ORDER if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else \
        [args.multi_pod]
    for mp in meshes:           # single-pod sweep completes first
        for arch in archs:
            for shape in shapes:
                cells.append((arch, shape, mp))

    failures = 0
    for arch, shape, mp in cells:
        tag = f"{arch}__{shape}__{'pod2' if mp else 'pod1'}__{args.preset}"
        path = outdir / f"{tag}.json"
        if args.skip_existing and path.exists() and \
                "error" not in json.loads(path.read_text()):
            continue
        try:
            rec = dryrun_cell(arch, shape, multi_pod=mp, preset=args.preset,
                              overrides=overrides, donate=args.donate,
                              scan_layers=args.scan)
        except Exception:
            failures += 1
            rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "error": traceback.format_exc()}
            print(f"[dryrun] FAILED {tag}")
            traceback.print_exc()
        path.write_text(json.dumps(rec, indent=2))
    print(f"[dryrun] wrote {len(cells)} records to {outdir}; "
          f"{failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
