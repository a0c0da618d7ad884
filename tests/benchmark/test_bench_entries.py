"""``BENCHMARK.json`` against its own rules, and the open-loop tail
reader, on the CPU:

- every metric has its reader (``benchmark/metrics/<name>.py``);
- every per-layer metric moves an end-to-end metric that each of its
  cells reports, and every cell it names exists;
- ``frame_p99_us.rr`` reads the window's frames as ``latency_p99_us``
  does, and nothing from a window without answers.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import byname  # noqa: E402
import run  # noqa: E402

BENCH = run.load_bench()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", [m["name"] for m in
                                  BENCH["end_to_end"] + BENCH["per_layer"]])
def test_every_metric_has_its_reader(name):
    assert callable(byname.module("metrics", name).read)


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_a_per_layer_metric_moves_what_its_cells_report(name):
    m = next(m for m in BENCH["per_layer"] if m["name"] == name)
    moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
    cells = m.get("workloads", CELLS)
    assert cells and set(cells) <= set(CELLS)
    for cell in cells:
        assert run.applies(moved, cell), (name, m["moves"], cell)


def _ctx(lat_ms, t0=100.0, seconds=20.0):
    """Frames due every 10 ms from ``t0``, each answered after its
    latency; one before the window and one failed frame besides."""
    frames = [(t0 + 0.01 * j, t0 + 0.01 * j, t0 + 0.01 * j + ms / 1e3, 1,
               None) for j, ms in enumerate(lat_ms)]
    frames.append((t0 - 1.0, t0 - 1.0, t0 + 5.0, 1, None))
    frames.append((t0 + 1.0, t0 + 1.0, t0 + 9.0, 1, True))
    return {"window": (t0, t0 + seconds, seconds), "frames": frames}


def test_the_tail_reader_reads_as_latency_p99_does():
    lat = [1.0] * 990 + [float(k) for k in range(100, 110)]
    ctx = _ctx(lat)
    tail = byname.module("metrics", "frame_p99_us.rr").read(ctx)
    assert tail == byname.module("metrics", "latency_p99_us").read(ctx)
    assert 1_000 < tail < 110_000
    assert byname.module("metrics", "frame_p99_us.rr").read(
        {"window": (0.0, 20.0, 20.0), "frames": []}) is None
