"""The trace reduction gives known numbers on a small trace."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import trace

CHIP = Path(__file__).resolve().parents[1]


def load(rel):
    spec = importlib.util.spec_from_file_location(rel.replace("/", "_"),
                                                  CHIP / rel)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def small_trace():
    """One traced step from 90 to 390 ns.  Device busy: [100, 150] and
    [200, 320] (two ops overlap), so 170 ns busy, 130 idle."""
    return trace.Trace(
        ops={0: [("fusion.1", 100, 50, {}),
                 ("paged_chunk_attention.3", 200, 100, {}),
                 ("fusion.2", 280, 40, {}),
                 ("fusion.9", 500, 10, {})]},          # after the window
        host=[("serve_step", 90, 300),
              ("PjitFunction(mixed_step)", 95, 20),
              ("wait", 340, 40),
              ("generator", 395, 5)])


def test_busy_window_and_idle():
    tr = small_trace()
    assert tr.window == (90, 390)
    assert trace.busy_s(tr) == pytest.approx(170e-9)
    assert trace.window_s(tr) == pytest.approx(300e-9)
    ctx = SimpleNamespace(trace=tr)
    idle = load("metrics/device_idle_share.py").read(ctx)
    assert idle == pytest.approx(100 * 130 / 300)


def test_top_ops_and_idle_gaps():
    tr = small_trace()
    top = trace.top_ops(tr)
    assert [n for n, _ in top] == ["paged_chunk_attention.3", "fusion.1",
                                   "fusion.2"]
    assert [s for _, s in top] == pytest.approx([100e-9, 50e-9, 40e-9])
    gaps = dict(trace.idle_gaps(tr))
    # [90, 100] inside the dispatch span, [150, 200] in the step only,
    # [320, 390] inside the wait.
    assert gaps == pytest.approx({"PjitFunction(mixed_step)": 10e-9,
                                  "serve_step": 50e-9, "wait": 70e-9})


SHAPES = {"layers": 2, "d": 8, "h": 2, "hkv": 1, "hd": 4, "ff": 16,
          "v": 10, "glu": True}


def ctx_for(tr):
    kmod = load("kernels/paged_chunk_attention.py")
    return SimpleNamespace(
        trace=tr, traced_steps=[{"rows": [(3, 2, True)]}], shapes=SHAPES,
        device={"peak_bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e10},
        kernel=lambda name: kmod, notes=[])


def test_roofline_and_mfu_readers():
    ctx = ctx_for(small_trace())
    # 2 layer calls, each bound by 176 bytes / 1e10 B/s; kernel time 100 ns.
    roof = load("metrics/paged_chunk_attention_roofline.py").read(ctx)
    assert roof == pytest.approx(100 * 2 * 176e-10 / 100e-9)
    assert "memory-bound" in ctx.notes[0]
    mfu = load("metrics/step_mfu.py").read(ctx)
    assert mfu == pytest.approx(100 * 5344 / (170e-9 * 1e12))


def test_no_kernel_event_means_no_value():
    tr = small_trace()
    tr.ops[0] = [o for o in tr.ops[0] if "chunk" not in o[0]]
    assert load("metrics/paged_chunk_attention_roofline.py").read(
        ctx_for(tr)) is None
    assert load("metrics/step_mfu.py").read(
        SimpleNamespace(trace=None, traced_steps=[])) is None


def test_recorded_chip_trace(tmp_path):
    """Three seconds of olmo-1b.agent-loop on one TPU v5e, traced by the
    harness: the reduction gives back what it gave on the chip."""
    import gzip
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(Path(__file__).parent / "data"
                   / "olmo-1b.agent-loop.xplane.pb.gz") as f:
        (d / "t.xplane.pb").write_bytes(f.read())
    tr = trace.load(str(tmp_path))
    assert len(tr.steps) == 22
    assert trace.busy_s(tr) == pytest.approx(2.963960429, rel=1e-9)
    assert trace.window_s(tr) == pytest.approx(3.077692675, rel=1e-9)
    kmod = load("kernels/paged_chunk_attention.py")
    hits = [o for o in tr.ops_in_window(0) if kmod.matches(o[0], o[3])]
    assert len(hits) == 22 * 16                 # one call per layer per step
    assert trace.top_ops(tr)[0][0] == "paged_chunk_attention.7"
    gaps = dict(trace.idle_gaps(tr))
    assert sum(gaps.values()) == pytest.approx(
        trace.window_s(tr) - trace.busy_s(tr), rel=1e-6)
