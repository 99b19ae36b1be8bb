"""The trace reduction on a small synthetic trace."""
import pytest

import devtrace as tr

MS = 1_000_000  # ns


def _trace():
    # one chip: two programs; the window is [10, 110) ms
    modules = [("jit__decode_fn(1)", 0 * MS, 20 * MS),
               ("jit__chunk_fn(2)", 30 * MS, 50 * MS),
               ("jit__decode_fn(1)", 60 * MS, 100 * MS)]
    ops = [("%_mmm_impl.1 = bf16[16,6912] custom-call(x)", 2 * MS, 18 * MS),
           ("%fusion.3 = f32[16] fusion(y)", 30 * MS, 40 * MS),
           ("%_mmm_impl.7 = bf16[32,6912] custom-call(z)", 40 * MS, 50 * MS),
           ("%_mmm_impl.1 = bf16[16,6912] custom-call(x)", 60 * MS, 90 * MS),
           ("%copy.2 = bf16[4] copy(w)", 90 * MS, 95 * MS)]
    host = [(tr.WINDOW_SPAN, 10 * MS, 110 * MS),
            ("perfbench.decode", 52 * MS, 58 * MS),
            ("perfbench.chunk", 96 * MS, 109 * MS)]
    return tr.Trace(devices=[tr.Device(modules=modules, ops=ops)], host=host)


def test_busy_idle_and_window():
    s = tr.summarize(_trace())
    assert s["window_s"] == pytest.approx(0.100)
    # busy in the window: 8 (clipped) + 10 + 10 + 30 + 5 ms
    assert s["busy_s"] == pytest.approx(0.063)


def test_program_and_kernel_time_by_containment():
    s = tr.summarize(_trace())
    assert s["module_s"]["jit__decode_fn"] == pytest.approx(0.050)
    assert s["module_s"]["jit__chunk_fn"] == pytest.approx(0.020)
    assert s["module_n"] == {"jit__chunk_fn": 1, "jit__decode_fn": 1}
    assert tr.kernel_time(s, "_mmm_impl", {"jit__decode_fn"}) == \
        pytest.approx(0.038)
    assert tr.kernel_time(s, "_mmm_impl") == pytest.approx(0.048)
    assert s["kernel_s"][("jit__chunk_fn", "fusion")] == pytest.approx(0.010)


def test_breakdown_names_ops_and_gaps_with_host_spans():
    s = tr.summarize(_trace())
    ops = s["breakdown"]["device_ops"]
    assert ops[0] == ["jit__decode_fn/_mmm_impl", pytest.approx(0.038)]
    gaps = s["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10
    longest = gaps[0]
    assert longest[1] == pytest.approx(0.015)          # 95 → 110 ms
    assert "perfbench.chunk" in longest[0]
    assert any("perfbench.decode" in g[0] and g[1] == pytest.approx(0.010)
               for g in gaps)                           # 50 → 60 ms


def test_busy_is_averaged_over_chips():
    t = _trace()
    t.devices.append(tr.Device(modules=[], ops=[]))
    assert tr.summarize(t)["busy_s"] == pytest.approx(0.0315)


def test_names():
    assert tr.module_base("jit__decode_fn(1829)") == "jit__decode_fn"
    assert tr.op_base("%_mmm_impl.12 = bf16[1,2] custom-call(a)") == \
        "_mmm_impl"
    assert tr.op_base("%fusion = f32[] fusion(a)") == "fusion"


def test_no_window_span_is_an_error():
    t = _trace()
    t.host = [h for h in t.host if h[0] != tr.WINDOW_SPAN]
    with pytest.raises(ValueError):
        tr.summarize(t)
