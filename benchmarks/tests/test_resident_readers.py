"""The four readers of the resident pool's append (PR 38) on planted span
lists and counters: `recover.readmit_share_pct`, `recover.readmit_ms_per_row`
(the warm recovery cell), `serving.readmit_ms_per_flush`,
`serving.row_slices_per_launch` (the serve cell). Each gives nothing, and
raises nothing, on what the parent's program leaves; the readers that were
there read the same with and without the new spans.

    python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import pytest
from test_recover_cell import _reader, _s
from test_recover_warm_cell import _ctx as _warm_ctx
from test_recover_warm_cell import _planted_trace

#: the append's legs a warm recovery lays, one chunk a pass: readmit 2 s
#: in each suffix replay, and one readmit outside the call that no reader
#: may count
RESIDENT_SPANS = [
    ("resident.launch", *_s(1.7, 1.9)),
    ("resident.readmit", *_s(2.1, 4.1)),
    ("resident.launch", *_s(6.9, 7.0)),
    ("resident.device-wait", *_s(7.0, 7.1)),
    ("resident.readmit", *_s(7.1, 9.1)),
    ("resident.readmit", *_s(11.0, 12.0)),
]
SUFFIX_ROWS = {"rebuild": 400, "verify": 400}


def _ms(lo: float, hi: float):
    return lo * 1e6, hi * 1e6


def _recover_ctx(new: bool = True) -> dict:
    trace = _planted_trace()
    if new:
        (line, main), = trace["_host_lines"]
        trace["_host_lines"] = [(line, main + RESIDENT_SPANS)]
    report = {"suffix_rows": dict(SUFFIX_ROWS)} if new else {}
    return _warm_ctx(trace=trace, passes=[
        {"events": 1000, "traced": True, "report": report},
        {"events": 1000, "traced": False, "report": report}])


def _serve_ctx(new: bool = True) -> dict:
    """A suffix flush of 20 ms (readmit 6 ms, device wait 6 ms) and a
    cold flush of 10 ms (device wait 3 ms)."""
    drain = [
        ("serving.flush", *_ms(30, 50)),
        ("serving.route", *_ms(30, 32)),
        ("resident.launch", *_ms(32, 34)),
        ("resident.device-wait", *_ms(35, 41)),
        ("resident.readmit", *_ms(41, 47)),
        ("serving.parity", *_ms(47, 49)),
        ("serving.flush", *_ms(60, 70)),
        ("serving.launch", *_ms(61, 63)),
        ("serving.device-wait", *_ms(63, 66)),
    ]
    if not new:
        drain = [e for e in drain
                 if e[0] not in ("resident.launch", "resident.readmit")]
    before = {"transactions": 100, "batched_launches": 10,
              "flush_s_total": 0.5, "queue_wait_s_total": 1.0}
    after = {"transactions": 300, "batched_launches": 50,
             "flush_s_total": 1.5, "queue_wait_s_total": 2.5}
    if new:
        before["row_slices"], after["row_slices"] = 20, 200
    return {"kind": "serve",
            "trace": {"_host_lines": [("python3", drain)]},
            "serving_before": before, "serving_after": after}


PLANTED = {
    # 2 + 2 s of readmit inside a call of 10 s
    "recover.readmit_share_pct": (40.0, _recover_ctx),
    # 4,000 ms over 800 rows
    "recover.readmit_ms_per_row": (5.0, _recover_ctx),
    # 6 ms over two flushes
    "serving.readmit_ms_per_flush": (3.0, _serve_ctx),
    # 180 slices in 40 launches
    "serving.row_slices_per_launch": (4.5, _serve_ctx),
}


@pytest.mark.parametrize("name", sorted(PLANTED))
def test_reader_gives_the_planted_value(name):
    value, ctx = PLANTED[name]
    assert _reader(name).read(ctx()) == pytest.approx(value)


@pytest.mark.parametrize("name", sorted(PLANTED))
def test_reader_gives_none_on_what_the_parent_leaves(name):
    read = _reader(name).read
    _value, ctx = PLANTED[name]
    # the parent: no `resident.launch` / `.readmit` span, no `row_slices`
    # in the tier's stats, no `suffix_rows` in the report
    assert read(ctx(new=False)) is None
    for kind in ("replay", "serve"):
        assert read({"kind": kind, "trace": None}) is None
    other = _serve_ctx if ctx is _recover_ctx else _recover_ctx
    assert read(other()) is None
    if ctx is _recover_ctx:   # a CPU's row slice is no chip's launch
        assert read(_recover_ctx() | {"rehearse": True}) is None


def test_a_share_per_row_needs_the_rows():
    ctx = _recover_ctx()
    for p in ctx["passes"]:
        p["report"] = {"suffix_rows": {}}
    assert _reader("recover.readmit_ms_per_row").read(ctx) is None
    assert _reader("recover.readmit_share_pct").read(ctx) == \
        pytest.approx(40.0)


@pytest.mark.parametrize("name, ctx", [
    ("serving.flush_host_ms_per_launch", _serve_ctx),
    ("recover.suffix_replay_share_pct", _recover_ctx),
    ("recover.hydrate_share_pct", _recover_ctx),
])
def test_the_readers_that_were_there_read_the_same_with_the_new_spans(
        name, ctx):
    read = _reader(name).read
    with_spans, without = read(ctx()), read(ctx(new=False))
    assert with_spans is not None
    assert with_spans == pytest.approx(without)
