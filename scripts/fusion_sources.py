#!/usr/bin/env python3
"""Which source line is `fusion.NNN`? Compiles the wave cycle (or, with
--preempt, the preemption burst) for a described TPU v5e — no chip needed,
`JAX_PLATFORMS=cpu` stays set — at the given capacities and prints, for every
fusion whose name or result type matches, the innermost frames of the
program's own code that its instructions carry (`stack_frame_id` in the
compiled module's text). At the flagship's capacities the fusion names are
the ones the chip's trace shows (`breakdown.device_ops`: checked on PR 30's
traced runs, and again on PR 35's), so PR 41's line's `fusion.695 s32[368640]`
became `interpod.py:_in_domain:150`, the gather that ended the round's `[S, N]`
in-domain count table (a `jit(take_along_axis)` traced twice keeps its FIRST
caller's frame: the `hold` gather beside it read the same line); since PR 42
that table is a product (`interpod.py:_same_domain_product`) and
`'s32\\[3686'` finds nothing, as `'s32\\[6553'`, the per-class aggregates
PR 35 took out. Takes 2-6 minutes for the flagship cycle.

    JAX_PLATFORMS=cpu python3 scripts/fusion_sources.py 's32\\[3686' 'f32\\[3686'
    JAX_PLATFORMS=cpu python3 scripts/fusion_sources.py --preempt 8 'pred\\[8,65536\\]'
    ... --dims N=1024,D=1024,P=30720,E=32768,SC=64,SL=64,S=8   (density-1k)
"""

import argparse
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FLAGSHIP = "N=5120,D=5120,P=53248,E=65536,SC=64,SL=64,S=72"


def compiled_text(dims: str, preempt: int) -> str:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from kubernetes_tpu.sched import prewarm
    from kubernetes_tpu.state.dims import Dims

    # a compile for a described device cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    d = Dims(**{k: int(v) for k, v in
                (kv.split("=") for kv in dims.split(","))})

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    if preempt:
        from kubernetes_tpu.sched.preemption import _preempt

        (tables, existing, cls, nnr, prio, keys, pdb, hw,
         ecfg) = on_chip(prewarm.abstract_preempt_args(d, preempt))
        lowered = _preempt.lower(tables, existing, cls, nnr, prio, d.D, keys,
                                 pdb, hw, ecfg)
    else:
        from kubernetes_tpu.sched.cycle import _schedule_batch_impl

        tables, pending, keys, existing, hw, ecfg = on_chip(
            prewarm.abstract_cycle_args(d)[:6])
        lowered = _schedule_batch_impl.lower(
            tables, pending, keys, d.D, existing, "waves", hw, ecfg, (), (),
            None, False)
    return lowered.compile().as_text()


def frame_tables(lines: list) -> tuple:
    """The module's FileNames / FunctionNames / FileLocations / StackFrames
    tables, each {id: text}."""
    tables = {}
    for i, ln in enumerate(lines[:5000]):
        if ln in ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames"):
            rows, j = {}, i + 1
            while j < len(lines) and re.match(r"\d+ ", lines[j]):
                k, v = lines[j].split(" ", 1)
                rows[int(k)] = v
                j += 1
            tables[ln] = rows
    return (tables["FileNames"], tables["FunctionNames"],
            tables["FileLocations"], tables["StackFrames"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("patterns", nargs="+",
                    help="regexes matched against `fusion.NNN type[shape]`")
    ap.add_argument("--dims", default=FLAGSHIP)
    ap.add_argument("--preempt", type=int, default=0, metavar="BURST")
    ap.add_argument("--depth", type=int, default=4)
    args = ap.parse_args()

    lines = compiled_text(args.dims, args.preempt).splitlines()
    files, funcs, locs, frames = frame_tables(lines)

    def chain(fid: int) -> list:
        out, seen = [], set()
        while fid in frames and fid not in seen and len(out) < args.depth:
            seen.add(fid)   # a root frame is its own parent
            loc, fid = (int(x) for x in re.search(
                r"file_location_id=(\d+) parent_frame_id=(\d+)",
                frames[fid]).groups())
            f, fn, line = re.search(
                r"file_name_id=(\d+) function_name_id=(\d+) line=(\d+)",
                locs[loc]).groups()
            name = files[int(f)].strip('"')
            if "kubernetes_tpu" in name:
                out.append(f"{os.path.basename(name)}:"
                           f"{funcs[int(fn)].strip(chr(34))}:{line}")
        return out

    bodies, cur = {}, None   # fused computation -> its instructions
    for ln in lines:
        m = re.match(r"(%?[\w.\-]+) \(.*\{\s*$", ln)
        if m:
            cur = bodies.setdefault(m.group(1).lstrip("%"), [])
        elif ln.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(ln)
    for ln in lines:
        m = re.match(r"\s*(?:ROOT )?%(fusion\.\d+) = (\w+\[[\d,]*\])", ln)
        if not m or not any(re.search(p, f"{m.group(1)} {m.group(2)}")
                            for p in args.patterns):
            continue
        called = re.search(r"calls=%([\w.\-]+)", ln).group(1)
        ids = sorted({int(i) for b in bodies.get(called, []) + [ln]
                      for i in re.findall(r"stack_frame_id=(\d+)", b)})
        where = sorted({" < ".join(chain(i)) for i in ids} - {""})
        print(f"{m.group(1)} {m.group(2)}: "
              f"{'; '.join(where) or 'no frame kept (a scatter: see the gather it feeds)'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
