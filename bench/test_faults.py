"""More runs of the harness with the timed path broken underneath (see
test_correct.py), in a file of their own so a parallel test run spreads
them: half of each coalesced batch left out, and windows the program
flags as cut."""
import pytest

from bench import drive
from bench.test_correct import _run, cut_once, cut_windows, drop_half, judged


@pytest.mark.parametrize("fault,rate,windows", [
    (drop_half, 400.0, False), (cut_windows, 30.0, True)],
    ids=["half-batch", "windows-cut"])
def test_harness_judges_a_broken_timed_path(fault, rate, windows,
                                            monkeypatch):
    """At 400 requests a second the scheduler coalesces most requests
    into batches of 2 to 4. With its rects materialised, a window that
    stays cut is still cut at the deadline, and the check judges it
    wrong."""
    monkeypatch.setattr(drive, "LATE_S", 3.0)
    judged(fault, rate, windows)


def test_client_asks_again_for_a_cut_window(monkeypatch):
    """With the rects materialised, a window cut the first time and
    whole when asked again is an exact answer, later: every sampled
    window was cut once, and the run is correct."""
    monkeypatch.setattr(drive, "LATE_S", 3.0)
    sess = []
    out = _run(lambda s: (cut_once(s), sess.append(s)), windows=True)
    assert sess[0].cut > 0
    assert out["correct"] is True, out["checks"]
