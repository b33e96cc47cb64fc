"""The readers of the scoring path's program spans, on made-up traces
whose means are known."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import spans  # noqa: E402
from bench.harness import trace as tr  # noqa: E402
from bench.harness.cell import metric_reader  # noqa: E402

READERS = {"admit_ms.serve": "serve.admit", "take_ms.serve": "serve.take",
           "pack_ms.serve": "serve.pack", "upload_ms.serve": "serve.upload",
           "launch_ms.serve": "serve.launch", "fetch_ms.serve": "serve.fetch",
           "resolve_ms.serve": "serve.resolve"}
MS = 1e6                                   # ns in a millisecond


def _run(host, start=10 * MS, window=100 * MS):
    trace = tr.Trace(start, window, {}, [(tr.WINDOW, start, window)] + host)
    return types.SimpleNamespace(trace=trace)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_means_its_span_in_the_window(metric):
    name, other = READERS[metric], "serve.elsewhere"
    read = metric_reader(metric)
    host = [
        (name, 5 * MS, 50 * MS),           # starts before the window
        (name, 20 * MS, 2 * MS),
        (name + "#replica=chip0,rows=3000#", 30 * MS, 4 * MS),
        (name, 109 * MS, 3 * MS),          # starts in, ends after
        (name, 111 * MS, 7 * MS),          # starts after the window
        (name + ".x", 40 * MS, 90 * MS),   # another span's name
        (other, 50 * MS, 80 * MS),
    ]
    assert read(_run(host)) == pytest.approx((2 + 4 + 3) / 3)
    assert read(_run([h for h in host if h[0] != name
                      and not h[0].startswith(name + "#")])) is None
    assert read(_run([])) is None


def test_durations_cut_metadata_and_keep_the_window():
    t = _run([("serve.take#k=v#", 10 * MS, 1.5 * MS),
              ("serve.take", 60 * MS, 2.5 * MS),
              ("serve.takes", 60 * MS, 9 * MS),
              ("serve.take", 9 * MS, 1 * MS)]).trace
    assert spans.durations_ns(t, "serve.take") == [1.5 * MS, 2.5 * MS]
    assert spans.mean_ms(t, "serve.take") == pytest.approx(2.0)
    assert spans.mean_ms(t, "serve.pack") is None
