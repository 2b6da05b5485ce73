"""Count the instructions of the two streamed block kernels' final LLO,
compiled for a TPU v5e that is described, not attached (no chip needed).

    PYTHONPATH=src python3 scripts/walk_llo.py [--dump DIR] [--json]

Compiles ``dcd_ell_block_pallas_call`` at rcv1's full shape (n 677,399,
k 73, d 47,236) and ``dcd_ragged_block_pallas_call`` at rcv1-tail's
(54,642,784 packed slots), both with the hinge loss, in a child process
whose ``LIBTPU_INIT_ARGS`` turns on Mosaic's dump (the flag is read when
libtpu starts).  It then reads each kernel's
``*-post-finalize-llo.txt`` and prints, per kernel, the scalar ops
(``s…`` and the predicate ops ``p…``), the vector ops (``v…``), the
integer divides (``sdiv``, ``srem``, ``sfloordiv``, ``sfloorrem``) and
the cross-lane permutes (``vperm``), and the divides and permutes inside
the loops that walk nonzeros.  Exits 2 where no v5e topology can be
described.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import pathlib
import re
import subprocess
import sys
import tempfile

KERNELS = ("_dcd_ell_stream_kernel", "_dcd_ragged_stream_kernel")
DIVIDES = ("sdiv", "srem", "sfloordiv", "sfloorrem")
# an op: `%x = llo.op`, `"llo.op"(…)` or a bare `llo.op` (a store)
_OP = re.compile(r"(?:^\s*|= |\")llo\.([a-z_0-9]+)", re.M)
# every walked nonzero masks its lane with one `vcmp.eq` against the
# lane iota: a loop body that holds one walks nonzeros
_LANE_MASK = "llo.vcmp.eq"

_CHILD = """
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from repro.core.duals import Hinge
from repro.dist.mesh import lane_pad
from repro.kernels.dcd_ell import (dcd_ell_block_pallas_call,
    dcd_ragged_block_pallas_call, ragged_stream_rows)

jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print(f"no v5e:2x2 topology can be described here: {e}")
    raise SystemExit(2)
chip = SingleDeviceSharding(topo.devices[0])
F32, I32, B, loss = jnp.float32, jnp.int32, 64, Hinge(C=1.0)

def compile_(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in shapes]
    jax.jit(fn).lower(*args).compile()

n, k, d = 677_399, 73, 47_236
compile_(lambda c, v, a, w, q, i: dcd_ell_block_pallas_call(
             (c, v), a, w, q, i, k=k, loss=loss),
         ((n, 1, lane_pad(k)), I32), ((n, 1, lane_pad(k)), F32),
         ((n,), F32), ((lane_pad(d + 1),), F32), ((n,), F32), ((B,), I32))
s = lane_pad(54_642_784)
compile_(lambda c, v, p, wid, a, w, q, i: dcd_ragged_block_pallas_call(
             ragged_stream_rows(c, v), p, wid, a, w, q, i, loss=loss),
         ((s,), I32), ((s,), F32), ((n,), I32), ((n,), I32), ((n,), F32),
         ((lane_pad(d + 1),), F32), ((n,), F32), ((B,), I32))
"""


def dump(out: pathlib.Path, timeout: float = 120.0) -> int:
    """Compile both kernels in a child with Mosaic's dump into ``out``;
    returns the child's exit code (2: no topology)."""
    env = dict(os.environ)
    env["LIBTPU_INIT_ARGS"] = (env.get("LIBTPU_INIT_ARGS", "")
                               + f" --xla_mosaic_dump_to={out}").strip()
    env.setdefault("TPU_LOG_DIR", "disabled")
    src = pathlib.Path(__file__).resolve().parent.parent / "src"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(src), env.get("PYTHONPATH", "")) if p)
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       timeout=timeout, capture_output=True, text=True)
    if r.returncode:
        sys.stderr.write(r.stdout[-4000:] + r.stderr[-4000:])
    return r.returncode


def final_llo(out: pathlib.Path, kernel: str) -> str:
    """The final LLO text of ``kernel`` in a Mosaic dump directory."""
    hits = sorted(p for p in out.rglob("*post-finalize-llo.txt")
                  if kernel in p.name)
    if not hits:
        raise FileNotFoundError(f"no final LLO of {kernel} under {out}")
    return hits[-1].read_text()


def _regions(llo: str) -> list:
    """(header, body) of every region of an LLO text, the body without
    the lines of the regions nested in it."""
    done, stack = [], [("", [])]
    for line in llo.splitlines():
        s = line.strip()
        if s.startswith("}"):
            done.append(stack.pop())
            if s.endswith("{"):  # `} else {`
                stack.append((s, []))
        elif s.endswith("{"):
            stack.append((s, []))
        else:
            stack[-1][1].append(s)
    return done + stack


def count(llo: str) -> dict:
    """Scalar (``s…``, and predicate ``p…``), vector (``v…``), divide and
    permute counts of one kernel's LLO; ``walk_divides`` and
    ``walk_vperm`` count only the bodies of the ``walk_loops`` loops
    that walk nonzeros (those holding a lane mask)."""
    ops = collections.Counter(_OP.findall(llo))
    walk, loops = collections.Counter(), 0
    for header, body in _regions(llo):
        text = "\n".join(body)
        if "scf.for" in header and _LANE_MASK in text:
            walk.update(_OP.findall(text))
            loops += 1
    return {
        "scalar_ops": sum(v for k, v in ops.items() if k[0] in "sp"),
        "vector_ops": sum(v for k, v in ops.items() if k[0] == "v"),
        "divides": sum(ops[k] for k in DIVIDES),
        "vperm": ops["vperm"],
        "walk_loops": loops,
        "walk_divides": sum(walk[k] for k in DIVIDES),
        "walk_vperm": walk["vperm"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dump", help="keep Mosaic's dump in this directory")
    ap.add_argument("--json", action="store_true",
                    help="one JSON line per kernel")
    args = ap.parse_args(argv)
    with tempfile.TemporaryDirectory() as tmp:
        out = pathlib.Path(args.dump or tmp).resolve()
        out.mkdir(parents=True, exist_ok=True)
        rc = dump(out)
        if rc:
            return rc
        for kernel in KERNELS:
            c = count(final_llo(out, kernel))
            if args.json:
                print(json.dumps({"kernel": kernel, **c}))
            else:
                print(f"{kernel:28s} " + "  ".join(
                    f"{k} {v}" for k, v in c.items()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
