"""collective.exposed_share: the part of the chips' busy time in the traced
window spent in collectives while no other operation runs, in %.  Both
numbers are means over the same device planes, so a plane with no
operations (the TPU trace's Megascale plane) cancels.  None when the trace
holds no busy time.

The trace names a device op after its HLO instruction, and XLA names the
all-reduce that a ``jax.lax`` collective lowers to after the primitive
(``psum.275``, ``pmax.20``): ``bench.trace`` knows collectives only by
words such as ``all-reduce``, so those ops are added here from their
summed time.  They are synchronous all-reduces on the device's one
sequential "XLA Ops" line, so no other op of that line runs beside them,
and their whole time is exposed, as ``bench.trace`` counts exposure."""
import sys

from bench import trace

# jax.lax collectives, as the first part of the instruction names they give
PRIMITIVES = ("psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_to_all")


def _by_primitive(name: str) -> bool:
    return name.split(".", 1)[0] in PRIMITIVES and not trace.is_collective(name)


def read(run):
    t = run.data.get("trace")
    if t is None or t.busy_s <= 0:
        return None
    named = {n: s for n, s in t.op_s.items() if _by_primitive(n)}
    exposed = t.exposed_collective_s + sum(named.values())
    top = sorted(named.items(), key=lambda kv: -kv[1])[:12]
    print(f"[collective] by words: {t.collective_s} s, exposed "
          f"{t.exposed_collective_s} s; by primitive name: {sum(named.values())} s "
          f"in {len(named)} ops, top {top}", file=sys.stderr)
    return 100.0 * exposed / t.busy_s
