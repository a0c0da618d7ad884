"""Python's garbage-collection pauses over the window, every
generation, in ms per second of window: 0 where none ran.  None where
the program does not time collections (no ``runtime`` stages)."""

GENERATIONS = ("gc-gen0", "gc-gen1", "gc-gen2")


def read(ctx):
    s0, s1 = ctx["stages"]
    after = s1.get("runtime")
    if not after:
        return None
    before = s0.get("runtime", {})
    pause = sum(after[g]["total-s"] - before.get(g, {"total-s": 0.0})
                ["total-s"] for g in GENERATIONS if g in after)
    return 1e3 * pause / ctx["window"][2]
