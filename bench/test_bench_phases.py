"""The phase spans and scopes as the benchmark reads them: the host-span
readers (``encode_ms``, ``batch_form_ms``, ``resolve_ms``), the scope walk
of ``bench/xspace.py`` and what it reads (the ``df_lookup`` scope and the
collectives), on made-up events and on recorded TPU v5e traces, and the
readers that were there before, which read what they read before."""

import os
from types import SimpleNamespace

import pytest

from bench import registry, xspace
from bench import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
FIRST = os.path.join(FIXTURES, "search_v5e.xplane.pb.gz")
SCOPED = os.path.join(FIXTURES, "search_scoped_v5e.xplane.pb.gz")
QUERY_PHASE = "jit__query_phase"


def _ev(name, s, e):
    return (name, s, e)


def _run(trace, **kw):
    return SimpleNamespace(trace=trace, **kw)


# ----------------------------------------------------------- the first trace
@pytest.fixture(scope="module")
def first():
    return tr.load(FIRST)


@pytest.mark.parametrize("name, value", [
    ("dispatch_ms", 314.75264),
    ("merge_rescore_ms", 5.9639426667),
    ("phase1_kernel_ms", 211.9112573),
    ("phase1_roofline_pct", 0.4829502531),
    ("device_idle_share", 14.3693791435),
])
def test_existing_readers_read_what_they_read_before(first, name, value):
    # the first recorded trace, read by the readers pinned to it;
    # dispatch_ms reads the engine's histogram, given here the trace's
    # three dispatches
    run = _run(first, cfg=registry.config("wiki-1chip"),
               device_kind="TPU v5 lite", dispatches=3,
               dispatch_s=sum(e - s for s, e in first.dispatches))
    assert registry.reader(name)(run) == pytest.approx(value, abs=1e-6)


@pytest.mark.parametrize("name", ["encode_ms", "batch_form_ms",
                                  "resolve_ms"])
def test_new_readers_are_silent_on_a_trace_without_the_spans(first, name):
    assert registry.reader(name)(_run(first)) is None


def test_a_loop_takes_the_scope_its_body_shares(first):
    scopes = xspace.load_scopes(FIRST)["/device:TPU:0"]
    loops = [op for op in first.chips[0].ops if op.name.startswith("while")]
    assert loops
    for op in loops:
        assert scopes[op.long] == ("jit(_query_phase)/vmap(vmap(jit("
                                   "searchsorted)))/while/body/closed_call")
    # before the scopes, the df lookup's only handle was its loops: the
    # scope they share reads the loops' time exactly
    loops_ms = tr.per_dispatch_ms(first, first.chips[0], (QUERY_PHASE,),
                                  lambda op: op.name.startswith("while"))
    assert loops_ms == pytest.approx(51.212154, abs=1e-6)
    assert xspace.scope_ms(first, xspace.load_scopes(FIRST),
                           "vmap(vmap(jit(searchsorted)))") == pytest.approx(
        loops_ms, rel=1e-12)
    assert xspace.scope_ms(first, xspace.load_scopes(FIRST),
                           "df_lookup") is None


# ------------------------------------------------------------- made-up spans
def _batcher_spans(d0, d1, encode=0.05):
    """One batch's spans around a dispatch from d0 to d1."""
    return [_ev("repro.engine.wait", d0 - 0.04, d0 - 0.03),
            _ev("repro.engine.batch_form", d0 - 0.03, d0),
            _ev(tr.DISPATCH_SPAN, d0, d1),
            _ev("repro.search.encode", d0 + 0.01, d0 + 0.01 + encode),
            _ev("repro.engine.readback", d1 - 0.02, d1),
            _ev("repro.engine.resolve", d1, d1 + 0.02)]


def test_host_span_readers_count_complete_dispatches_only():
    host = ([_ev(tr.WINDOW_SPAN, 0.0, 3.0)]
            # cut by the window's start: its spans do not count
            + _batcher_spans(-0.5, 0.5, encode=0.4)
            + _batcher_spans(1.0, 1.5, encode=0.1)
            + _batcher_spans(2.0, 2.5, encode=0.2)
            # cut by the window's end
            + _batcher_spans(2.9, 3.4, encode=0.3))
    device = {0: {"XLA Ops": [_ev("%fusion.1 = f32[8] fusion()", 1.0, 1.2)]}}
    r = tr.reduce_events(device, host)
    assert len(r.dispatches) == 2
    run = _run(r)
    assert registry.reader("encode_ms")(run) == pytest.approx(150.0)
    assert registry.reader("batch_form_ms")(run) == pytest.approx(30.0)
    assert registry.reader("resolve_ms")(run) == pytest.approx(20.0)


def test_host_span_readers_without_a_complete_dispatch():
    host = [_ev(tr.WINDOW_SPAN, 0.0, 1.0)] + _batcher_spans(0.9, 1.4)
    device = {0: {"XLA Ops": [_ev("%fusion.1 = f32[8] fusion()", 0.1, 0.2)]}}
    run = _run(tr.reduce_events(device, host))
    for name in ("encode_ms", "batch_form_ms", "resolve_ms"):
        assert registry.reader(name)(run) is None


# ------------------------------------------------------------ made-up scopes
_PSUM = "%psum.7 = s32[128,800] all-reduce(s32[128,800] %add.3)"
_DF = "%while.13 = (s32[]) while((s32[]) %tuple.1)"
_AG = "%all-gather.12 = f32[128,1280] all-gather(f32[128,320] %p.1)"
_TOPK = "%top_k.6 = f32[128,10] custom-call(f32[128,1280] %all-gather.12)"
_SCOPES = {_PSUM: "jit(_query_phase)/shard_map/idf_psum/psum",
           _DF: "jit(_query_phase)/shard_map/df_lookup/vmap(vmap(jit("
                "searchsorted)))/while/body/closed_call",
           _AG: "jit(_merge_select)/merge_select/top_k",
           _TOPK: "jit(_merge_select)/merge_select/top_k"}


def _chip(psum_s, ag_s):
    return {"XLA Modules": [_ev("jit__query_phase(1)", 1.0, 2.0),
                            _ev("jit__merge_select(2)", 2.0, 2.5)],
            "XLA Ops": [_ev(_DF, 1.0, 1.3), _ev(_PSUM, 1.3, 1.3 + psum_s),
                        _ev(_AG, 2.0, 2.0 + ag_s), _ev(_TOPK, 2.4, 2.5)]}


def _scoped(n_chips):
    device = {i: _chip(0.01 * (i + 1), 0.1) for i in range(n_chips)}
    host = [_ev(tr.WINDOW_SPAN, 0.0, 3.0), _ev(tr.DISPATCH_SPAN, 0.5, 2.9)]
    scope_map = {f"/device:TPU:{i}": dict(_SCOPES) for i in range(n_chips)}
    return tr.reduce_events(device, host), scope_map


def test_df_lookup_scope_per_dispatch():
    r, scope_map = _scoped(2)
    assert xspace.scope_ms(r, scope_map, "df_lookup") == pytest.approx(300.0)
    # the top-k is in merge_select but not in the query phase's runs
    assert xspace.scope_ms(r, scope_map, "merge_select") is None


def test_collective_ms_reads_the_busiest_chip():
    r, scope_map = _scoped(4)
    # chip 3: psum 40 ms + all-gather 100 ms; the top-k is no collective
    assert xspace.collective_ms(r, scope_map) == pytest.approx(140.0)


def test_collective_ms_is_silent_on_one_chip():
    r, scope_map = _scoped(1)
    assert xspace.collective_ms(r, scope_map) is None
    assert xspace.scope_ms(r, scope_map, "df_lookup") == pytest.approx(300.0)


# --------------------------------------------------------- the wire format
def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if n:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def test_scope_walk_on_a_made_up_xspace():
    stat_meta = [(5, _msg((1, k), (2, _msg((1, k), (2, name)))))
                 for k, name in ((1, "tf_op"), (2, "flops"),
                                 (3, "jit(f)/phase1/mul:"))]

    def meta(mid, name, *stats):
        return (4, _msg((1, mid), (2, _msg((1, mid), (2, name),
                                           *((5, st) for st in stats)))))

    body = "jit(f)/df_lookup/while/body/"
    ev_meta = [
        # 10: a loop with no tf_op; 11 and 12 in its body; 13 by reference
        meta(10, "%while.1 = () while()", _msg((1, 2), (4, 7))),
        meta(11, "%add.1 = f32[] add()", _msg((1, 1), (5, body + "add:"))),
        meta(12, "%gather.1 = f32[] gather()",
             _msg((1, 1), (5, body + "gather:"))),
        meta(13, "%mul.1 = f32[] multiply()", _msg((1, 1), (7, 3))),
        meta(14, "%copy.1 = f32[] copy()"),
    ]
    events = [_msg((1, mid), (2, off), (3, dur)) for mid, off, dur in
              ((10, 100, 50), (11, 110, 5), (12, 120, 5), (13, 200, 5),
               (14, 300, 5))]
    line = _msg((2, "XLA Ops"), *((4, e) for e in events))
    plane = _msg((2, "/device:TPU:0"), (3, line), *ev_meta, *stat_meta)
    other = _msg((2, "/host:CPU"))
    got = xspace.scopes(_msg((1, plane), (1, other)))
    assert got == {"/device:TPU:0": {
        "%while.1 = () while()": "jit(f)/df_lookup/while/body",
        "%add.1 = f32[] add()": "jit(f)/df_lookup/while/body/add",
        "%gather.1 = f32[] gather()": "jit(f)/df_lookup/while/body/gather",
        "%mul.1 = f32[] multiply()": "jit(f)/phase1/mul"},
        "/host:CPU": {}}


# ---------------------------------------------------------- the scoped trace
@pytest.fixture(scope="module")
def scoped():
    return tr.load(SCOPED)


def test_fixtures_are_small():
    for path in (FIRST, SCOPED):
        assert os.path.getsize(path) < 256 * 1024, path


def test_scoped_trace_reads_every_phase(scoped):
    # 1 s of wiki1-closed's window traced on a v5e with this program
    assert len(scoped.chips) == 1 and len(scoped.dispatches) == 2
    run = _run(scoped)
    for name in ("encode_ms", "batch_form_ms", "resolve_ms"):
        v = registry.reader(name)(run)
        assert v is not None and v > 0, name
    scope_map = xspace.load_scopes(SCOPED)
    df = xspace.scope_ms(scoped, scope_map, "df_lookup")
    loops = tr.per_dispatch_ms(scoped, scoped.chips[0], (QUERY_PHASE,),
                               lambda op: op.name.startswith("while"))
    assert df is not None and loops
    assert abs(df - loops) <= 0.1 * loops
    assert xspace.scope_ms(scoped, scope_map, "phase1") >= tr.phase1_kernel_ms(
        scoped)
    assert xspace.collective_ms(scoped, scope_map) is None
    # every idle gap from the first complete dispatch to the last falls in
    # a host span; the window's edges lose the ops and spans still running
    # when the profiler started or stopped
    first, last = scoped.dispatches[0][0], scoped.dispatches[-1][1]
    inside = [(s + e) / 2 for s, e in scoped.chips[0].gaps
              if first <= (s + e) / 2 <= last]
    assert inside
    assert all(scoped.label(t) != "no host span" for t in inside)
