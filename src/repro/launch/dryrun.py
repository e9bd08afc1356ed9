import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above run before ANY other import — jax locks the platform and
the device count at first init, and the production meshes need 512 host
devices.  The CPU platform is explicit, and the ``--all`` children inherit
it: they compile against virtual host devices, and on a machine with a
TPU none of them may try to take the chip.  (Only this entry point does
so; tests and benches see 1 device.)

Per cell:
    with mesh:
        lowered  = jax.jit(step, in_shardings=...).lower(*input_specs(...))
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for the roofline

plus a collective-bytes pass over the optimized HLO (all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute operand
sums — cost_analysis does not report these).  One JSON artifact per cell
lands in ``--out`` for launch/roofline.py and EXPERIMENTS.md.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod both]
"""

import argparse
import json
import re
import subprocess
import sys
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, get_config
from repro.launch.mesh import make_production_mesh, make_rules
from repro.launch.specs import cell_is_skipped, input_specs
from repro.distributed.sharding import activation_policy, tree_shardings

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_text: str) -> int:
    """Sum the byte sizes of every 'dtype[dims]' in a result-type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_COLL_RE = re.compile(r"%?[\w.\-]+\s*=\s*(.+?)\s+(" + "|".join(_COLLECTIVES)
                      + r")(?:-start|-done)?\(")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_WHILE_RE = re.compile(r"while\(.*?(?:condition|cond)=%?([\w.\-]+),\s*"
                       r"body=%?([\w.\-]+)")
_WHILE_RE2 = re.compile(r"while\(.*?body=%?([\w.\-]+),\s*"
                        r"(?:condition|cond)=%?([\w.\-]+)")
_CALL_RE = re.compile(r"(?:to_apply|called_computations)=\{?%?([\w.\-]+)")
_CONST_RE = re.compile(r"%([\w.\-]+)\s*=\s*s32\[\]\s*constant\((\d+)\)")
_ROOT_CMP_RE = re.compile(r"ROOT\s+%?[\w.\-]+\s*=\s*pred\[\]\s*compare\("
                          r"%?([\w.\-]+),\s*%?([\w.\-]+)\)")


def _parse_computations(hlo_text: str) -> tuple:
    """Split optimized HLO into computations; find ENTRY."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line.strip())
        if m and not line.startswith(" "):
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
            continue
        if cur is not None and line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line.strip())
    return comps, entry


def _trip_count(comp_lines: list) -> int:
    """Trip count of a while condition: the s32 constant in the ROOT
    compare (scan/fori loops compare the induction var against the bound).
    Falls back to 1 (don't multiply) when unrecognized."""
    consts = {}
    for ln in comp_lines:
        m = _CONST_RE.search(ln)
        if m:
            consts[m.group(1)] = int(m.group(2))
    for ln in comp_lines:
        m = _ROOT_CMP_RE.search(ln)
        if m:
            for op in (m.group(2), m.group(1)):
                if op in consts:
                    return max(1, consts[op])
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Collective operand bytes from optimized HLO — both static (each op
    once) and execution-weighted (x while trip counts, recovered from the
    loop-condition compare constants; scan bodies appear once in HLO but
    run n_periods x n_microbatch x ... times)."""
    comps, entry = _parse_computations(hlo_text)
    static = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    weighted = {k: 0.0 for k in _COLLECTIVES}

    def comp_collectives(name):
        out = []
        for ln in comps.get(name, ()):
            m = _COLL_RE.match(ln)
            if m:
                out.append((m.group(2), _shape_bytes(m.group(1))))
        return out

    visited_static = set()
    for name in comps:
        for kind, b in comp_collectives(name):
            static[kind] += b
            counts[kind] += 1

    def walk(name, mult, seen):
        if name not in comps or name in seen:
            return
        seen = seen | {name}
        for kind, b in comp_collectives(name):
            weighted[kind] += b * mult
        for ln in comps[name]:
            wm = _WHILE_RE.search(ln) or _WHILE_RE2.search(ln)
            if wm:
                a, b2 = wm.group(1), wm.group(2)
                cond, body = (a, b2) if _WHILE_RE.search(ln) else (b2, a)
                # XLA annotates analyzed loops directly:
                tm = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', ln)
                trips = int(tm.group(1)) if tm else \
                    _trip_count(comps.get(cond, []))
                walk(body, mult * trips, seen)
                continue
            cm = _CALL_RE.search(ln)
            if cm and not _COLL_RE.match(ln):
                walk(cm.group(1), mult, seen)

    if entry:
        walk(entry, 1.0, set())
    total_weighted = sum(weighted.values())
    return {"bytes": static, "counts": counts,
            "total_bytes": sum(static.values()),
            "weighted_bytes": {k: float(v) for k, v in weighted.items()},
            "total_weighted_bytes": float(total_weighted)}


def _memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if ma is None:
        return {}
    out = {}
    for name in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(ma, name, None)
        if v is not None:
            out[name] = int(v)
    if not out:
        out["repr"] = repr(ma)
    return out


def _cost_analysis_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if ca is None:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float))}


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             save_hlo: bool = False, variant: str = "baseline") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if variant != "baseline":
        mesh_name += f"__{variant}"
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "variant": variant, "status": "unknown"}
    skip = cell_is_skipped(arch, shape_name)
    if skip:
        record.update(status="skipped", reason=skip)
        return _write(record, out_dir)

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        rules = make_rules(mesh)
        cell = input_specs(arch, shape_name, rules, variant=variant)
        rules = cell.rules or rules
        shardings = tuple(
            tree_shardings(s, mesh) if not isinstance(s, jax.sharding.PartitionSpec)
            else jax.NamedSharding(mesh, s)
            for s in cell.in_specs)
        with mesh, activation_policy(rules):
            # donate train state / decode caches: the functional update
            # aliases its input buffers (in-place on real hardware)
            out_shardings = None
            if cell.out_specs is not None:
                out_shardings = jax.tree.map(
                    lambda s: (jax.NamedSharding(mesh, s)
                               if isinstance(s, jax.sharding.PartitionSpec)
                               else s),
                    cell.out_specs,
                    is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
                    or x is None)
                out_shardings = tuple(out_shardings)
            jitted = jax.jit(cell.step_fn, in_shardings=shardings,
                             donate_argnums=cell.donate,
                             out_shardings=out_shardings)
            lowered = jitted.lower(*cell.args_sds)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = _memory_analysis_dict(compiled)
            cost = _cost_analysis_dict(compiled)
            print(f"[{arch} {shape_name} {mesh_name}] memory_analysis:",
                  {k: f"{v/2**30:.3f}GiB" for k, v in mem.items()
                   if isinstance(v, int)})
            print(f"[{arch} {shape_name} {mesh_name}] cost_analysis flops:",
                  cost.get("flops"))
            hlo = compiled.as_text()
            coll = collective_bytes(hlo)
        record.update(
            status="ok", kind=cell.kind, notes=cell.notes,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            devices=int(mesh.size), memory_analysis=mem, cost_analysis=cost,
            collectives=coll,
        )
        if save_hlo:
            os.makedirs(out_dir, exist_ok=True)
            hp = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.hlo")
            with open(hp, "w") as f:
                f.write(hlo)
            record["hlo_path"] = hp
    except Exception as e:
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    return _write(record, out_dir)


def _write(record: dict, out_dir: str) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    name = f"{record['arch']}__{record['shape']}__{record['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(record, f, indent=1)
    status = record["status"]
    extra = record.get("reason", record.get("error", ""))
    print(f"[dryrun] {record['arch']} x {record['shape']} x {record['mesh']}"
          f" -> {status} {extra[:200]}")
    return record


def run_all(out_dir: str, meshes: list, archs=None, shapes=None,
            jobs: int = 1) -> int:
    """Spawn one subprocess per cell (isolates compile memory)."""
    cells = []
    for arch in (archs or ARCHS):
        for shape in (shapes or SHAPES):
            for mp in meshes:
                cells.append((arch, shape, mp))
    failures = 0
    running = []
    for (arch, shape, mp) in cells:
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape, "--out", out_dir]
        if mp:
            cmd.append("--multi-pod")
        running.append(((arch, shape, mp), subprocess.Popen(cmd)))
        while len(running) >= jobs:
            done = [(c, p) for c, p in running if p.poll() is not None]
            if not done:
                time.sleep(2)
                continue
            for c, p in done:
                running.remove((c, p))
                if p.returncode != 0:
                    failures += 1
                    print(f"[dryrun] SUBPROCESS FAILED: {c}")
    for c, p in running:
        if p.wait() != 0:
            failures += 1
            print(f"[dryrun] SUBPROCESS FAILED: {c}")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--meshes", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "optimized", "optimized_nocast",
                             "optimized_noshard"])
    args = ap.parse_args()

    if args.all:
        meshes = {"single": [False], "multi": [True],
                  "both": [False, True]}[args.meshes]
        sys.exit(1 if run_all(args.out, meshes, jobs=args.jobs) else 0)

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    rec = run_cell(args.arch, args.shape, args.multi_pod, args.out,
                   save_hlo=args.save_hlo, variant=args.variant)
    sys.exit(0 if rec["status"] in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()
