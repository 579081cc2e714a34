"""The benchmark's ``reordered_share.hi`` reader, loaded by path, on a small
recorded span log: the share of the window's high-priority segments with
``ahead`` > 0, and None where the segment spans lack the field."""
import collections
import importlib.util
import pathlib
import sys
from types import SimpleNamespace

import pytest

from repro.core import spans

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"


@pytest.fixture
def reader():
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    spec = importlib.util.spec_from_file_location(
        "reordered_share_hi", BENCH / "metrics" / "reordered_share.hi.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    spans.clear()
    yield module.read
    spans.clear()


RUN = SimpleNamespace(t0=10.0, t_end=20.0, seconds=10.0)


def segment(instance, seq, priority, start, ahead=None):
    """A segment span as the engine writes it; ``ahead`` None leaves the
    field out."""
    row = (spans.SEGMENT, instance, seq, f"svc{priority}", priority, 0,
           False, start - 0.002, start - 0.001, start, start + 0.0002,
           start + 0.001, start + 0.0011, start + 0.0012)
    return row if ahead is None else row + (ahead,)


def test_share_of_hi_segments_taken_ahead(reader):
    for row in (segment(1, 0, 0, 11.0, 0), segment(1, 1, 0, 11.1, 1),
                segment(2, 0, 0, 11.2, 0), segment(2, 1, 0, 11.3, 2),
                segment(3, 0, 5, 11.4, 3),       # lo: not counted
                segment(4, 0, 0, 25.0, 1)):      # after the window
        spans.record(row)
    assert reader(RUN) == pytest.approx(50.0)


def test_none_where_the_window_holds_no_segment(reader):
    spans.record(segment(1, 0, 0, 25.0, 1))
    assert reader(RUN) is None


def test_none_on_spans_without_the_ahead_field(reader, monkeypatch):
    fields = spans.Segment._fields[:-1]
    assert spans.Segment._fields[-1] == "ahead"
    monkeypatch.setitem(spans.LAYOUTS, spans.SEGMENT,
                        collections.namedtuple("Segment", fields))
    for i in range(3):
        spans.record(segment(1, i, 0, 11.0 + 0.1 * i))
    assert reader(RUN) is None
