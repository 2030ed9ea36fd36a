"""The trace reduction on a recorded TPU v5e trace and on made-up events."""

import gzip
import os

import pytest

from bench import registry
from bench import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "search_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    return tr.load(FIXTURE)


def test_fixture_is_small():
    assert os.path.getsize(FIXTURE) < 256 * 1024


def test_recorded_trace_busy_idle_and_programs(recorded):
    # 1 s of wiki1-closed's window traced on a v5e: batches of 128
    # queries over one chip's 1,045,376-doc shard, back to back
    assert len(recorded.chips) == 1
    chip = recorded.chips[0]
    assert recorded.window_s == pytest.approx(1.000573369, abs=1e-9)
    assert chip.busy_s == pytest.approx(0.856797188, abs=1e-9)
    assert 0 < chip.busy_s < recorded.window_s
    assert len(chip.in_modules("jit__query_phase")) == 3
    assert len(chip.in_modules("jit__merge_select")) == 3
    assert len(chip.in_modules("jit__rescore")) == 4
    # three dispatches lie wholly inside the window; the first rescore
    # belongs to the dispatch the window cut, and is not in one
    assert len(recorded.dispatches) == 3
    for prog in ("jit__query_phase", "jit__merge_select", "jit__rescore"):
        assert len(chip.in_modules(prog, recorded.dispatches)) == 3
    idle = sum(e - s for s, e in chip.gaps)
    assert idle == pytest.approx(recorded.window_s - chip.busy_s, abs=1e-9)


def test_recorded_trace_kernel_time(recorded):
    # the fused phase-1 Pallas kernel ran once per search, 211.91 ms each
    assert tr.phase1_kernel_ms(recorded) == pytest.approx(211.9112573,
                                                          abs=1e-6)
    top = recorded.device_ops(3)
    assert top[0][0] == "fused_phase1_pallas.1 (tpu_custom_call)"
    assert top[0][1] == pytest.approx(0.635733772, abs=1e-9)


def test_recorded_kernel_time_read_directly():
    """The same kernel time summed straight from the raw events."""
    from jax.profiler import ProfileData

    with gzip.open(FIXTURE, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = next(l for l in plane.lines if l.name == "XLA Ops")
    ns = sum(e.duration_ns for e in ops.events
             if 'custom_call_target="tpu_custom_call"' in e.name)
    assert ns * 1e-6 / 3 == pytest.approx(tr.phase1_kernel_ms(
        tr.load(FIXTURE)), rel=1e-9)


def test_idle_gaps_are_labelled(recorded):
    gaps = recorded.idle_gaps(5)
    assert len(gaps) == 5
    assert all(label.startswith("chip0: ") for label, _ in gaps)
    # the engine's dispatch span and the readback label some of them
    labels = {label for label, _ in gaps}
    assert {"chip0: repro.engine.dispatch",
            "chip0: np.asarray(jax.Array)"} <= labels
    assert [d for _, d in gaps] == sorted((d for _, d in gaps), reverse=True)


def _ev(name, s, e):
    return (name, s, e)


def test_synthetic_nesting_and_window():
    device = {
        0: {"XLA Modules": [_ev("jit__query_phase(1)", 1.0, 3.0),
                            _ev("jit__merge_select(2)", 3.5, 4.0),
                            _ev("jit__rescore(3)", 4.0, 4.1)],
            "XLA Ops": [
                _ev('%k.1 = f32[8] custom-call(), '
                    'custom_call_target="tpu_custom_call"', 1.0, 2.0),
                _ev("%while.1 = (s32[]) while()", 2.0, 3.0),
                _ev("%fusion.9 = f32[8] fusion()", 2.1, 2.4),  # nested
                _ev("%all-gather.3 = f32[8] all-gather()", 3.5, 3.75)]},
        1: {"XLA Modules": [_ev("jit__query_phase(1)", 1.0, 2.0)],
            "XLA Ops": [_ev("%psum.7 = s32[8] all-reduce(%sub.1)",
                            1.0, 1.5),
                        _ev("%copy.2 = f32[8] copy(%all-gather.1)",
                            1.6, 1.7)]},
    }
    host = [_ev(tr.WINDOW_SPAN, 0.5, 4.5),
            _ev("repro.engine.dispatch", 0.9, 4.1),
            _ev("np.asarray(jax.Array)", 3.2, 3.6)]
    r = tr.reduce_events(device, host)
    assert r.window_s == pytest.approx(4.0)
    c0, c1 = r.chips
    assert c0.busy_s == pytest.approx(2.25)     # nested op counted once
    assert c1.busy_s == pytest.approx(0.6)
    assert r.busy_s == pytest.approx(1.425)
    assert [op.name for op in c0.ops] == ["k.1", "while.1", "all-gather.3"]
    assert r.dispatches == [(pytest.approx(0.4), pytest.approx(3.6))]
    assert tr.phase1_kernel_ms(r) == pytest.approx(1000.0)
    # the gap 3.0-3.5 on chip 0 sits inside the readback span
    assert r.label(3.25 - 0.5) == "np.asarray(jax.Array)"
    gaps = r.idle_gaps()
    assert gaps[0][0] == "chip1: repro.engine.dispatch"
    assert gaps[0][1] == pytest.approx(2.8)
    assert ["chip1: no host span", pytest.approx(0.5)] in gaps


def test_layer_readers_on_made_up_events():
    device = {
        0: {"XLA Modules": [_ev("jit__query_phase(1)", 1.0, 2.0),
                            _ev("jit__merge_select(2)", 2.0, 2.5),
                            _ev("jit__rescore(3)", 2.5, 2.6)],
            "XLA Ops": [_ev("%psum.1 = s32[8] all-reduce(%a)", 1.0, 1.1),
                        _ev("%all-gather.2 = f32[8] all-gather(%b)",
                            2.0, 2.3)]},
        1: {"XLA Modules": [_ev("jit__query_phase(1)", 1.0, 2.0),
                            _ev("jit__merge_select(2)", 2.0, 2.5)],
            "XLA Ops": [_ev("%psum.1 = s32[8] all-reduce(%a)", 1.0, 1.6),
                        _ev("%fusion.1 = f32[8] fusion(%b)", 2.0, 2.5)]},
    }
    r = tr.reduce_events(device, [_ev(tr.WINDOW_SPAN, 0.0, 3.0),
                                  _ev(tr.DISPATCH_SPAN, 0.5, 2.9)])
    run = type("Run", (), {"trace": r})()
    assert registry.reader("merge_rescore_ms")(run) == pytest.approx(600.0)
    # no Pallas kernel ran: the kernel metrics stay silent
    assert registry.reader("phase1_kernel_ms")(run) is None
    assert registry.reader("device_idle_share")(run) == pytest.approx(
        100 * (1 - (0.4 + 1.1) / 2 / 3.0))


def test_per_dispatch_readers_count_complete_dispatches_only():
    kernel = ('%k.1 = f32[8] custom-call(), '
              'custom_call_target="tpu_custom_call"')
    device = {0: {
        "XLA Modules": [_ev("jit__query_phase(1)", 0.1, 0.8),
                        _ev("jit__query_phase(1)", 1.1, 1.8),
                        _ev("jit__query_phase(1)", 2.1, 2.8),
                        _ev("jit__merge_select(2)", 2.8, 2.85)],
        "XLA Ops": [_ev(kernel, 0.1, 0.8), _ev(kernel, 1.1, 1.5),
                    _ev(kernel, 2.1, 2.7)]}}
    host = [_ev(tr.WINDOW_SPAN, 0.0, 3.0),
            # the window cuts the first dispatch: its kernel does not count
            _ev(tr.DISPATCH_SPAN, -0.5, 0.9),
            _ev(tr.DISPATCH_SPAN, 1.0, 1.9),
            _ev(tr.DISPATCH_SPAN, 2.0, 2.9)]
    r = tr.reduce_events(device, host)
    assert len(r.dispatches) == 2
    assert tr.phase1_kernel_ms(r) == pytest.approx((400 + 600) / 2)
    run = type("Run", (), {"trace": r})()
    # one merge in two dispatches (the rescore never ran): 25 ms each
    assert registry.reader("merge_rescore_ms")(run) == pytest.approx(25.0)
    # without a complete dispatch the per-dispatch readers stay silent
    r0 = tr.reduce_events(device, host[:2])
    assert r0.dispatches == [] and tr.phase1_kernel_ms(r0) is None


def test_overlapping_program_runs_count_each_op_once():
    # a second, shorter event of the same program inside a run must
    # neither drop the kernel that outlasts it nor count it twice
    kernel = ('%k.1 = f32[8] custom-call(), '
              'custom_call_target="tpu_custom_call"')
    device = {0: {
        "XLA Modules": [_ev("jit__query_phase(1)", 1.1, 1.8),
                        _ev("jit__query_phase(1)", 1.2, 1.3)],
        "XLA Ops": [_ev(kernel, 1.25, 1.7)]}}
    host = [_ev(tr.WINDOW_SPAN, 0.0, 3.0), _ev(tr.DISPATCH_SPAN, 1.0, 1.9)]
    r = tr.reduce_events(device, host)
    assert tr.phase1_kernel_ms(r) == pytest.approx(450.0)


def test_no_device_events_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events({}, [])
