"""Debug CLI: top HBM-byte and collective contributors of a dry-run cell.

    PYTHONPATH=src python -m repro.parallel.hlo_debug --arch X --shape Y [--multi-pod]
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
from collections import defaultdict

import jax

from repro.parallel import hlo as H


def top_contributors(text: str, k: int = 15):
    comps, entry = H.parse_hlo(text)
    edges = defaultdict(list)
    fus, app = set(), set()
    for comp in comps.values():
        for op in comp.ops:
            m = dict(H._CALL_RE.findall(op.line))
            if op.op == "while":
                trips = H._trip_count(comps.get(m.get("condition")), op.line)
                if m.get("body"):
                    edges[m["body"]].append((comp.name, float(trips)))
            elif op.op == "fusion" and m.get("calls"):
                edges[m["calls"]].append((comp.name, 1.0)); fus.add(m["calls"])
            elif m.get("to_apply"):
                edges[m["to_apply"]].append((comp.name, 1.0)); app.add(m["to_apply"])
    cache = {}
    def mult(n, d=0):
        if n == entry: return 1.0
        if n in cache: return cache[n]
        if d > 200 or n not in edges: return 1.0
        cache[n] = sum(mult(c, d + 1) * w for c, w in edges[n]) or 1.0
        return cache[n]

    brows, crows = [], []
    for comp in comps.values():
        m = mult(comp.name)
        shapes = {op.var: op.type_str for op in comp.ops}
        skip_bytes = comp.name in fus or comp.name in app
        for op in comp.ops:
            base = op.op.replace("-start", "")
            if base in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                        "collective-permute") and not op.op.endswith("-done"):
                _, r = H._shape_elems_bytes(op.type_str)
                crows.append((r * m, r, m, base, op.line.strip()[:140]))
            if skip_bytes or op.op in H._SKIP_BYTES_OPS or op.op.endswith("-done"):
                continue
            _, rb = H._shape_elems_bytes(op.type_str)
            if op.op in H._WINDOW_BYTES_OPS:
                b = 2 * rb
            elif op.op in H._UPDATE_BYTES_OPS:
                ub = H._shape_elems_bytes(shapes.get(op.operands[1], ""))[1] if len(op.operands) > 1 else 0
                b = 2 * (ub or rb)
            else:
                b = rb + sum(H._shape_elems_bytes(shapes.get(nm, ""))[1] for nm in op.operands)
            brows.append((b * m, b, m, op.op, comp.name[:35], op.var[:45]))
    brows.sort(reverse=True); crows.sort(reverse=True)
    print(f"== top HBM bytes (total {sum(r[0] for r in brows)/2**40:.2f} TiB) ==")
    for r in brows[:k]:
        print(f"{r[0]/2**30:9.2f} GiB (x{r[2]:6.0f} of {r[1]/2**20:8.1f} MiB) {r[3]:20s} {r[5]} @{r[4]}")
    print(f"== top collectives (total {sum(r[0] for r in crows)/2**40:.2f} TiB) ==")
    for r in crows[:k]:
        print(f"{r[0]/2**30:9.2f} GiB (x{r[2]:6.0f}) {r[3]:16s} {r[4]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--q-chunk", type=int, default=1024)
    ap.add_argument("--k-chunk", type=int, default=1024)
    args = ap.parse_args()
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import wire_cell
    from repro.models.lm import PerfKnobs

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    cell = wire_cell(cfg, mesh, seq_len=shape.seq_len, global_batch=shape.global_batch,
                     mode=shape.kind, knobs=PerfKnobs(q_chunk=args.q_chunk, k_chunk=args.k_chunk))
    with jax.set_mesh(mesh):
        compiled = cell.lower().compile()
    top_contributors(compiled.as_text(), args.top)


if __name__ == "__main__":
    main()
