"""The comparison that decides ``correct``.

A sample of a run's reads, drawn from the seed, is judged against the
reference on the same points: ``oracle.Oracle`` over the whole set
where the points live on the host, ``windows.Reference`` (the Oracle
over candidate windows) where they were drawn on the device. Every
answer the configuration promises is exact, so each compared number
has the limit 0:

  wrong_answers    answers that differ from the reference's
  missing_answers  requests that raised or never completed

A materialised window (RangeQuery, CircleQuery(materialize=True)) is
right only when its ``ok`` flag is true and its count and ids are the
reference's: a window the program flags as cut is not an exact answer
(the client asks again until it gets one, ``bench/drive.py``).
"""
from __future__ import annotations

import numpy as np

from bench.drive import materialised
from bench.oracle import Oracle

LIMITS = {"wrong_answers": 0, "missing_answers": 0}


def _same(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and \
        a.tobytes() == b.tobytes()


def _window_ids(orc, spec, args, q):
    if spec.kind == "range":
        return np.sort(orc.rect_ids(args[0][q])).astype(np.int32)
    return np.sort(orc.circle_ids(args[0][q], args[1][q],
                                  args[2][q])).astype(np.int32)


def judge(orc: Oracle, req, out) -> bool:
    """Whether ``out`` is the reference's answer to read ``req``."""
    spec, args = req.spec, req.args
    kind = spec.kind
    if kind == "point":
        return _same(np.asarray(out, bool), orc.point(*args))
    if kind == "range_count":
        want = np.array([len(orc.rect_ids(r)) for r in args[0]], np.int32)
        return _same(np.asarray(out, np.int32), want)
    if materialised(spec):
        cnt, vids, ok = (np.asarray(a) for a in out)
        for q in range(len(args[0])):
            want = _window_ids(orc, spec, args, q)
            v = vids[q]
            if (not ok[q] or int(cnt[q]) != len(want)
                    or not _same(np.sort(v[v >= 0]), want)):
                return False
        return True
    if kind == "circle":
        cx, cy, r = args
        want = np.array([len(orc.circle_ids(cx[q], cy[q], r[q]))
                         for q in range(len(cx))], np.int32)
        return _same(np.asarray(out, np.int32), want)
    if kind == "knn":
        # the neighbours' exact distances must be the k smallest
        vids = np.asarray(out[1])
        for q in range(len(args[0])):
            want = orc.knn_d2(args[0][q], args[1][q], spec.k)
            got = np.sort(orc.vid_d2(vids[q], args[0][q], args[1][q]))
            if len(np.unique(vids[q])) != spec.k or not _same(got, want):
                return False
        return True
    raise ValueError(f"no reference for {kind!r}")


def answer(orc: Oracle, req):
    """The reference's own answer to read ``req``, in the program's
    form: what the control puts in the program's place."""
    spec, args = req.spec, req.args
    kind = spec.kind
    if kind == "point":
        return orc.point(*args)
    if kind == "range_count":
        return np.array([len(orc.rect_ids(r)) for r in args[0]], np.int32)
    if materialised(spec):
        rows = [_window_ids(orc, spec, args, q) for q in range(len(args[0]))]
        w = max(1, max(len(r) for r in rows))
        vids = np.full((len(rows), w), -1, np.int32)
        for q, r in enumerate(rows):
            vids[q, :len(r)] = r
        return (np.array([len(r) for r in rows], np.int32), vids,
                np.ones(len(rows), bool))
    if kind == "circle":
        cx, cy, r = args
        return np.array([len(orc.circle_ids(cx[q], cy[q], r[q]))
                         for q in range(len(cx))], np.int32)
    if kind == "knn":
        vids = np.stack([orc.knn_ids(args[0][q], args[1][q], spec.k)
                         for q in range(len(args[0]))]).astype(np.int32)
        return (None, vids)
    raise ValueError(f"no reference for {kind!r}")


class Verdict:
    """The compared numbers of one run, and the wrong answers by
    family."""

    def __init__(self):
        self.counts = {"compared": 0, "wrong_answers": 0,
                       "missing_answers": 0}
        self.wrong = {}

    def note(self, family: str, right: bool):
        self.counts["compared"] += 1
        if not right:
            self.counts["wrong_answers"] += 1
            self.wrong[family] = self.wrong.get(family, 0) + 1


def compare(answers, orc: Oracle, control: Oracle = None) -> Verdict:
    """Judge ``answers``, (request, answer, done) triples, against
    ``orc``. With ``control``, the control's answers replace the
    program's."""
    v = Verdict()
    for req, out, done in answers:
        if control is not None:
            out, done = answer(control, req), True
        if not done:
            v.counts["missing_answers"] += 1
            continue
        v.note(req.family, judge(orc, req, out))
    return v
