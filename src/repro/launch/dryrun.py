"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
against 512 placeholder host devices; capture memory/cost/collective
analysis for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

A CPU-only tool: the placeholder devices are XLA:CPU host devices, so it
sets ``JAX_PLATFORMS=cpu`` before JAX is imported and never touches an
accelerator. The roofline terms use the v5e row of ``obs.prof.PEAKS``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --out results/
"""
# These lines MUST run before any other import (jax locks the platform
# and the device count on first init):
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.distributed import sharding
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.obs.prof import PEAKS
from repro.training import AdamWConfig, init_opt_state, make_train_step

V5E = PEAKS["TPU v5 lite"]

# skip list (DESIGN.md §4): pure full-attention archs have no sub-quadratic
# path for 524k decode.
LONG_CTX_OK = {"gemma3-4b", "hymba-1.5b", "falcon-mamba-7b"}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[^\]]*\]))\S*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\b")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_BYTES = {"f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2,
          "u16": 2}
# effective wire multiplier per collective (ring algorithms)
_WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(spec: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(spec):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str):
    """Per-device wire bytes by collective kind, parsed from the
    post-SPMD optimized HLO (shapes there are already per-device)."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        spec, kind = m.group(1), m.group(2)
        out[kind] = out.get(kind, 0.0) + _shape_bytes(spec) * _WIRE_FACTOR[kind]
    return out


# ---------------------------------------------------------------------------


def build_lowerable(arch: str, shape_name: str, *, unroll: bool):
    """Returns (fn, kwargs_specs, in_shardings, out_shardings, meta).

    unroll=True unrolls the layer scans for exact cost_analysis FLOP/byte
    counts (XLA counts a scan body once, not x trip-count); unroll=False
    keeps the runtime lax.scan program whose memory_analysis reflects the
    deployed executable."""
    from repro.models import transformer as _T
    _T.UNROLL_SEGMENTS = unroll
    cfg = get_config(arch)
    model = build_model(cfg)
    shape = INPUT_SHAPES[shape_name]
    mesh = sharding.current_mesh()

    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        step = make_train_step(model, opt_cfg, remat=True)
        params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        state_shapes = {"params": params_shapes,
                        "opt": jax.eval_shape(init_opt_state, params_shapes)}
        batch = model.input_specs(shape)
        state_sh = _state_shardings(state_shapes, mesh)
        batch_sh = _batch_shardings(batch, mesh)
        fn = step
        args = (state_shapes, batch)
        in_sh = (state_sh, batch_sh)
        out_sh = (state_sh, None)
        n_tok = shape.global_batch * shape.seq_len
        model_flops = 6.0 * cfg.active_param_count() * n_tok
    elif shape.kind == "prefill":
        def fn(params, batch):
            logits, cache = model.prefill(params, batch)
            return logits, cache
        params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        batch = model.input_specs(shape)
        in_sh = (sharding.param_shardings(params_shapes, mesh),
                 _batch_shardings(batch, mesh))
        out_sh = None
        args = (params_shapes, batch)
        n_tok = shape.global_batch * shape.seq_len
        model_flops = 2.0 * cfg.active_param_count() * n_tok
    else:  # decode
        def fn(params, batch):
            return model.decode(params, batch["cache"], batch["tokens"])
        params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        batch = model.input_specs(shape)
        in_sh = (sharding.param_shardings(params_shapes, mesh),
                 _batch_shardings(batch, mesh))
        out_sh = None
        args = (params_shapes, batch)
        n_tok = shape.global_batch  # one token per sequence
        model_flops = 2.0 * cfg.active_param_count() * n_tok

    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "params": cfg.param_count(), "active_params": cfg.active_param_count(),
            "tokens": n_tok, "model_flops": model_flops}
    return fn, args, in_sh, out_sh, meta


def _state_shardings(state_shapes, mesh):
    p_sh = sharding.param_shardings(state_shapes["params"], mesh)
    m_sh = sharding.param_shardings(state_shapes["opt"]["m"], mesh)
    v_sh = sharding.param_shardings(state_shapes["opt"]["v"], mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    step_sh = NamedSharding(mesh, P())
    return {"params": p_sh, "opt": {"m": m_sh, "v": v_sh, "step": step_sh}}


def _batch_shardings(batch, mesh):
    from jax.sharding import NamedSharding

    def one(path, leaf):
        name = str(getattr(path[-1], "key", ""))
        nd = len(leaf.shape)
        if name in ("tokens",):
            axes = ("batch",) + (None,) * (nd - 1)
        elif name in ("img_embeds", "frames"):
            axes = ("batch", None, "embed")
        elif name in ("k", "v", "ck", "cv", "k_s", "v_s"):
            # §Perf iteration: head-shard the cache when kv_heads divides
            # the 'model' axis (TP attention, no softmax collectives);
            # otherwise shard the cache LENGTH over 'model' (flash-decode
            # style) instead of replicating — 4.8x memory-term win for
            # kv=8 archs (internlm2/yi/dbrx) on the 16-wide axis.
            kv_heads = leaf.shape[3] if nd >= 4 else leaf.shape[-1]
            divisible = kv_heads % mesh.shape.get("model", 1) == 0
            seq_ax = "kv_seq" if divisible else "cache_len"
            axes = (None, "batch", seq_ax, "kv_heads", None)[:nd]
        elif name == "conv":
            axes = (None, "batch", None, "d_inner")
        elif name == "h":
            axes = (None, "batch", "d_inner", None)
        else:  # pos etc.
            axes = (None,) * nd
        spec = sharding.spec_for(leaf.shape, axes, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, batch)


def _lower_compile(arch, shape_name, unroll):
    from repro.tuning import FLAGS
    fn, args, in_sh, out_sh, meta = build_lowerable(arch, shape_name,
                                                    unroll=unroll)
    donate = ()
    if meta["kind"] == "decode" and FLAGS["donate_cache"]:
        donate = (1,)
    jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                  donate_argnums=donate)
    lowered = jfn.lower(*args)
    return lowered.compile(), meta


def run_one(arch: str, shape_name: str, multi_pod: bool, *,
            save_hlo_dir=None, verbose=True, costs: bool = True):
    """costs=False (multi-pod pass): only prove lower+compile+fits with
    the runtime scanned program; the single-pod roofline pass adds the
    unrolled compile for exact per-op accounting."""
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    sharding.activate_mesh(mesh)
    try:
        with mesh:
            # pass 1 (runtime program, scanned): memory truth
            compiled_scan, meta = _lower_compile(arch, shape_name, False)
            # pass 2 (unrolled): exact per-op cost/collective accounting
            compiled = (_lower_compile(arch, shape_name, True)[0]
                        if costs else compiled_scan)
        ca = compiled.cost_analysis()
        ma = compiled_scan.memory_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        n_dev = mesh.size
        flops_dev = float(ca.get("flops", 0.0))
        bytes_dev = float(ca.get("bytes accessed", 0.0))
        coll_dev = float(sum(coll.values()))
        res = dict(meta)
        res.update(
            mesh="2x16x16" if multi_pod else "16x16",
            n_devices=n_dev,
            ok=True,
            seconds=round(time.time() - t0, 1),
            flops_per_device=flops_dev,
            bytes_per_device=bytes_dev,
            collective_bytes_per_device=coll_dev,
            collectives={k: int(v) for k, v in coll.items()},
            compute_s=flops_dev / V5E.flops_per_s,
            memory_s=bytes_dev / V5E.bytes_per_s,
            # a ring collective drives one of the chip's four ICI links
            collective_s=coll_dev / (V5E.ici_bytes_per_s / 4),
            model_flops_per_device=meta["model_flops"] / n_dev,
            useful_flops_ratio=(meta["model_flops"] / n_dev) / max(flops_dev, 1.0),
            arg_bytes_per_device=getattr(ma, "argument_size_in_bytes", None),
            temp_bytes_per_device=getattr(ma, "temp_size_in_bytes", None),
            out_bytes_per_device=getattr(ma, "output_size_in_bytes", None),
        )
        terms = {"compute": res["compute_s"], "memory": res["memory_s"],
                 "collective": res["collective_s"]}
        res["dominant"] = max(terms, key=terms.get)
        if save_hlo_dir:
            os.makedirs(save_hlo_dir, exist_ok=True)
            tag = f"{arch}_{shape_name}_{res['mesh']}".replace("/", "-")
            with open(os.path.join(save_hlo_dir, tag + ".hlo"), "w") as f:
                f.write(hlo)
        if verbose:
            print(f"[OK] {arch:22s} {shape_name:12s} {res['mesh']:7s} "
                  f"compute={res['compute_s']*1e3:9.2f}ms "
                  f"memory={res['memory_s']*1e3:9.2f}ms "
                  f"coll={res['collective_s']*1e3:9.2f}ms "
                  f"dom={res['dominant']:10s} "
                  f"useful={res['useful_flops_ratio']:.2f} "
                  f"temp={(res['temp_bytes_per_device'] or 0)/2**30:.2f}GiB "
                  f"({res['seconds']}s)", flush=True)
        return res
    except Exception as e:  # noqa
        if verbose:
            print(f"[FAIL] {arch} {shape_name} multi_pod={multi_pod}: {e}",
                  flush=True)
            traceback.print_exc()
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16", "ok": False,
                "error": str(e)[:2000]}
    finally:
        sharding.activate_mesh(None)


def pairs(include_long_skips=False):
    for arch in ARCH_IDS:
        for shape in INPUT_SHAPES:
            if shape == "long_500k" and arch not in LONG_CTX_OK:
                if include_long_skips:
                    yield arch, shape, "skip"
                continue
            yield arch, shape, "run"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="append jsonl results here")
    ap.add_argument("--skip-multi-pod-costs", action="store_true",
                    default=True)
    ap.add_argument("--tune", default=None,
                    help="comma k=v tuning flags (repro.tuning.FLAGS)")
    ap.add_argument("--hlo-dir", default=None)
    args = ap.parse_args()

    if args.tune:
        from repro.tuning import FLAGS
        for kv in args.tune.split(","):
            k, v = kv.split("=")
            cur = FLAGS[k]
            if isinstance(cur, bool):
                FLAGS[k] = v in ("1", "True", "true")
            elif isinstance(cur, int):
                FLAGS[k] = int(v)
            elif isinstance(cur, float):
                FLAGS[k] = float(v)
            else:
                FLAGS[k] = v
        print("tuning:", {k: v for k, v in FLAGS.items()})
    todo = []
    if args.all:
        for arch, shape, status in pairs():
            if status == "run":
                todo.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    for arch, shape in todo:
        for mp in meshes:
            res = run_one(arch, shape, mp, save_hlo_dir=args.hlo_dir,
                          costs=not (mp and args.skip_multi_pod_costs))
            results.append(res)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(res) + "\n")
    n_ok = sum(r.get("ok") for r in results)
    print(f"\n{n_ok}/{len(results)} lowered+compiled OK")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
