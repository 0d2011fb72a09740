"""The split of a traced step's device time by the program's layer scopes
(bench/scopes.py) and the metric readers that read it, on hand-made HLO,
operations and traces; and the map of the tiny cells' real steps, compiled on
the CPU."""
import gzip
import time
from pathlib import Path

import pytest

import bench_fixtures  # puts the checkout on sys.path
from bench_fixtures import tiny_root  # noqa: F401 (a fixture)
from bench import harness, scopes, spec
from bench.families import qwen3
from bench import trace as tr

HLO = """HloModule jit_decode_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "layers.py"

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(decode_step)/layers/while/body/closed_call/mlp/mul" stack_frame_id=3}
}

%fused_computation.2 (param_0: f32[8]) -> (f32[8], f32[8]) {
  %param_0 = f32[8]{0} parameter(0)
  %mul.5 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(decode_step)/layers/while/body/closed_call/attn_qkv/mul"}
  %convert.6 = f32[8]{0} convert(%mul.5)
  %sub.7 = f32[8]{0} subtract(%param_0, %param_0), metadata={op_name="jit(decode_step)/layers/while/body/closed_call/attn_qkv/sub"}
  ROOT %tuple.8 = (f32[8]{0}, f32[8]{0}) tuple(%convert.6, %sub.7)
}

%fused_computation.3 (param_0: f32[8]) -> (f32[8], f32[8]) {
  %param_0 = f32[8]{0} parameter(0)
  %mul.9 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(decode_step)/layers/while/body/closed_call/attn_qkv/mul"}
  %add.10 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(decode_step)/layers/while/body/closed_call/mlp/add"}
  ROOT %tuple.11 = (f32[8]{0}, f32[8]{0}) tuple(%mul.9, %add.10)
}

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode_step)/layers/while/body/closed_call/mlp/mul" stack_frame_id=3}
  %copy.2 = f32[8]{0} copy(%fusion.1)
  %sub_fusion.12 = (f32[8]{0}, f32[8]{0}) fusion(%p), kind=kLoop, calls=%fused_computation.2
  %add_fusion.13 = (f32[8]{0}, f32[8]{0}) fusion(%p), kind=kLoop, calls=%fused_computation.3
  ROOT %dynamic-slice.4 = f32[8]{0} dynamic-slice(%copy.2), metadata={op_name="jit(decode_step)/layers/while/body/dynamic_slice"}
}
"""
DATA = Path(__file__).parent / "data"


@pytest.mark.parametrize("op_name,scope", [
    ("jit(s)/layers/while/body/closed_call/attn_qkv/bsd,dhk->bshk/dot_general", "attn_qkv"),
    ("jit(s)/layers/while/body/closed_call/attn_core/kv_write/scatter", "kv_write"),
    ("jit(s)/layers/while/body/closed_call/mlp/dot_general", "mlp"),
    ("jit(s)/layers/while/body/dynamic_slice", "layers"),
    ("jit(s)/head/mlp_block/dot_general", "head"),
    ("jit(s)/add", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
    (None, scopes.UNSCOPED),
])
def test_innermost_scope_wins(op_name, scope):
    assert scopes.scope_of(op_name, qwen3.SCOPES) == scope


def test_hlo_scopes_maps_every_instruction():
    module, m = scopes.hlo_scopes(HLO, qwen3.SCOPES)
    assert module == "jit_decode_step"
    assert m["fusion.1"] == "mlp"  # a fusion counts under its root's scope
    assert m["multiply.3"] == "mlp"
    assert m["dynamic-slice.4"] == "layers"  # the scan's own slicing
    assert m["copy.2"] == scopes.UNSCOPED  # inserted by XLA: no op_name
    assert m["p"] == scopes.UNSCOPED
    # a multi-output fusion's root is a tuple with no op_name: the scope its
    # fused instructions share, where they share one
    assert m["sub_fusion.12"] == "attn_qkv"
    assert m["add_fusion.13"] == scopes.UNSCOPED
    with pytest.raises(ValueError):
        scopes.hlo_scopes("not HLO", qwen3.SCOPES)


@pytest.mark.parametrize("vocab,scope", [
    (qwen3.SCOPES, "mlp"), ((*qwen3.SCOPES, "expert"), "expert")])
def test_a_familys_own_scope_counts_under_itself(vocab, scope):
    """A scope that one family's program adds (here ``expert``, inside
    ``mlp``) counts under itself in that family's vocabulary, and under the
    scope around it in qwen3's."""
    hlo = HLO.replace("closed_call/mlp/mul", "closed_call/mlp/expert/mul")
    _, m = scopes.hlo_scopes(hlo, vocab)
    assert m["fusion.1"] == m["multiply.3"] == scope
    assert m["dynamic-slice.4"] == "layers"
    assert scopes.scope_of("jit(s)/layers/while/body/closed_call/mlp/expert/dot",
                           vocab) == scope


@pytest.mark.parametrize("step,expect", [
    ("decode_32k", {"dynamic-slice_bitcast_fusion.4": "layers",
                    "bitcast_dynamic-update-slice_fusion.5": "layers",
                    "copy.104": scopes.UNSCOPED, "fusion.144": "attn_core",
                    "bitcast_add_fusion.3": "mlp", "convolution_bitcast_fusion": "head"}),
    ("prefill_16k", {"fusion.151": "attn_core", "select_select_fusion.2": "attn_core",
                     "fusion.142": "mlp", "fusion.141": "attn_out",
                     "fusion.133": "attn_qkv", "subtract_convert_fusion.4": "attn_qkv"}),
])
def test_hlo_scopes_of_the_chips_compiled_steps(step, expect):
    """The optimized HLO of qwen3-0.6b's steps as the TPU v5e's compiler gave
    it; the operations are the longest of each cell's traced step."""
    with gzip.open(DATA / f"tpu_v5e.qwen3-0.6b.{step}.hlo.gz", "rt") as f:
        module, m = scopes.hlo_scopes(f.read(), qwen3.SCOPES)
    assert module == f"jit_{step.split('_')[0]}_step"
    assert {k: m[k] for k in expect} == expect


def _planes():
    return [("/device:TPU:0", [
        ("XLA Modules", [("jit_decode_step(7)", 0.0, 10.0),
                         ("jit__lambda(9)", 10.0, 12.0),
                         ("jit_decode_step(7)", 12.0, 22.0)]),
        ("XLA Ops", [("%fusion.14 = f32[8] fusion(...)", -1e-9, 0.5),
                     ("%while.5 = (s32[]) while(...)", 0.5, 8.0),
                     ("%fusion.1 = f32[8] fusion(...)", 1.0, 3.0),
                     ("%dynamic-slice.4 = f32[8] dynamic-slice(...)", 3.0, 8.0),
                     ("%copy.2 = f32[8] copy(...)", 8.0, 9.5),
                     ("%fusion.1 = f32[8] fusion(...)", 10.5, 11.5),
                     ("%fusion.1 = f32[8] fusion(...)", 13.0, 14.0)])])]


def test_module_ops_leave_out_other_modules():
    """``fusion.1`` of the argmax's module shares its name with the step's:
    only the step module's events hold the step's operations. ``fusion.14``
    starts a nanosecond before its module's event: its midpoint is inside."""
    ops = scopes.module_ops(_planes(), "jit_decode_step")
    assert ops == {"/device:TPU:0": [("fusion.14", -1e-9, 0.5),
                                     ("while.5", 0.5, 8.0), ("fusion.1", 1.0, 3.0),
                                     ("dynamic-slice.4", 3.0, 8.0),
                                     ("copy.2", 8.0, 9.5), ("fusion.1", 13.0, 14.0)]}
    assert scopes.module_ops(_planes(), "jit_prefill_step") == {}
    assert scopes.by_scope({}, {}, (0.0, 1.0)) == {}


def test_buckets_sum_to_the_steps_leaf_time():
    _, m = scopes.hlo_scopes(HLO, qwen3.SCOPES)
    ops = scopes.module_ops(_planes(), "jit_decode_step")
    ops["/device:TPU:1"] = [("fusion.1", 0.0, 4.0), ("copy.9", 4.0, 6.0)]
    window = (0.0, 13.5)
    s = scopes.by_scope(ops, m, window)
    # the loop is not counted beside its body; the window cuts fusion.1 at
    # 13.5 and fusion.14 at 0; copy.9 and fusion.14 are in no map: unscoped;
    # means over two devices
    assert s == pytest.approx({"mlp": (2.0 + 0.5 + 4.0) / 2, "layers": 5.0 / 2,
                               "unscoped": (0.5 + 1.5 + 2.0) / 2})
    leaf = tr.reduce(ops, [], window).op_s
    assert sum(s.values()) == pytest.approx(sum(leaf.values()), rel=1e-12)


def _run(kind="decode", scope_s=None, traced=True):
    summary = tr.Summary(window_s=1.0, devices=1, busy_s=0.9)
    if scope_s is not None:
        summary.scope_s = scope_s
    return harness.Run(kind=kind, chips=1, peak=None, setup_s=1.0, window_s=1.0,
                       steps=20, items=40, step_s=[0.05] * 20,
                       trace=summary if traced else None, traced_steps=10)


def _read(name, run):
    return spec.load_reader(bench_fixtures.REPO, name)(run)


def test_readers_read_ms_per_traced_step():
    run = _run(scope_s={"layers": 0.4, "unscoped": 0.1, "attn_core": 0.02,
                        "mlp": 0.03})
    assert _read("layer_carry_ms.decode", run) == pytest.approx(40.0)
    assert _read("unscoped_ms.decode", run) == pytest.approx(10.0)
    assert _read("attention_ms.decode", run) == pytest.approx(2.0)
    assert _read("mlp_ms.decode", run) == pytest.approx(3.0)
    assert _read("kv_write_ms.decode", run) is None  # an empty bucket is not 0
    assert _read("attention_ms.prefill", run) is None
    run = _run("prefill", scope_s={"attn_core": 1.2, "mlp": 0.06})
    assert _read("attention_ms.prefill", run) == pytest.approx(120.0)
    assert _read("mlp_ms.prefill", run) == pytest.approx(6.0)
    assert _read("mlp_ms.decode", run) is None


@pytest.mark.parametrize("name", scopes.METRICS)
@pytest.mark.parametrize("case", ["no trace", "no scope map", "nothing scoped"])
def test_readers_return_none_without_scopes(name, case):
    kind = name.rsplit(".", 1)[1]
    run = {"no trace": _run(kind, traced=False),
           "no scope map": _run(kind),
           "nothing scoped": _run(kind, scope_s={"unscoped": 0.5})}[case]
    assert _read(name, run) is None


SPLIT = {  # scope: (operation, seconds in the traced window)
    "decode": {"attn_core": ("fusion.1", 0.1), "kv_write": ("fusion.2", 0.02),
               "mlp": ("fusion.3", 0.08), "layers": ("dynamic-slice.4", 0.4),
               "unscoped": ("copy.5", 0.2)},
    "prefill": {"attn_core": ("fusion.1", 0.5), "mlp": ("fusion.3", 0.3)},
}
READS = {"layer_carry_ms": "layers", "unscoped_ms": "unscoped",
         "attention_ms": "attn_core", "kv_write_ms": "kv_write", "mlp_ms": "mlp"}


def _step_hlo(kind):
    lines = [f"HloModule jit_{kind}_step, is_scheduled=true", "",
             "ENTRY %main.1 (p: f32[8]) -> f32[8] {", "  %p = f32[8]{0} parameter(0)"]
    for scope, (op, _) in SPLIT[kind].items():
        path = {"layers": "layers/while/body/dynamic_slice", "unscoped": None}.get(
            scope, f"layers/while/body/closed_call/{scope}/x")
        meta = f', metadata={{op_name="jit({kind}_step)/{path}"}}' if path else ""
        lines.append(f"  %{op} = f32[8]{{0}} fusion(%p), kind=kLoop{meta}")
    return "\n".join(lines + ["}"])


def _step_trace(kind):
    """One device running the step's module over [0, 1] s, its operations
    one after another, and two traced steps on the host."""
    ops, t = [], 0.0
    for op, secs in SPLIT[kind].values():
        ops.append((f"%{op} = f32[8] fusion(...)", t, t + secs))
        t += secs
    return [("/device:TPU:0", [("XLA Modules", [(f"jit_{kind}_step(3)", 0.0, 1.0)]),
                               ("XLA Ops", ops)]),
            ("/host:CPU", [("python", [("bench.step", 0.0, 0.5),
                                       ("bench.step", 0.5, 1.0)])])]


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_the_split_reaches_the_readers_on_the_result_line(kind, tiny_root, monkeypatch):
    """A traced run splits the trace by the map of the step compiled after
    the window and after the peak memory is read, while the loop still holds
    its state; every scope metric of the cell is on the result line, in ms
    per traced step. The trace is made by hand (a CPU trace has no TPU)."""
    seen = []
    real_window, real_peak = harness.run_window, harness.memory_peak

    def run_window(loop, seconds, counter, trace_steps=0, trace_dir=None):
        window_s, items, step_s, _ = real_window(loop, seconds, counter)
        return window_s, items, step_s, _step_trace(kind)

    def memory_peak(mesh):
        seen.append("memory_peak")
        return real_peak(mesh)

    def step_hlo(loop):
        seen.append("step_hlo" if hasattr(loop, "params") else "step_hlo after finish")
        return _step_hlo(kind)

    monkeypatch.setattr(harness, "run_window", run_window)
    monkeypatch.setattr(harness, "memory_peak", memory_peak)
    monkeypatch.setattr(scopes, "step_hlo", step_hlo)
    args = harness.parse(["--workload", f"tiny.{kind}", "--seed", "5", "--seconds", "0.5",
                          "--trace", "1"])
    result = harness.run(args, time.perf_counter(), root=tiny_root, allow_cpu=True)
    assert seen == ["memory_peak", "step_hlo"]
    assert result["correct"], result["checks"]
    steps = spec.load_cell(f"tiny.{kind}", tiny_root).mix["trace_steps"]
    want = {f"{reader}.{kind}": 1e3 * SPLIT[kind][scope][1] / steps
            for reader, scope in READS.items() if scope in SPLIT[kind]}
    assert len(want) == {"decode": 5, "prefill": 2}[kind]
    got = {k: v["value"] for k, v in result["metrics"].items() if k in scopes.METRICS}
    assert got == pytest.approx(want)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_the_tiny_steps_map_to_every_scope(kind, tiny_root):
    """The map of the step the loop ran, fetched from its live state as a
    traced run does after the window."""
    cell = spec.load_cell(f"tiny.{kind}", tiny_root)
    mesh = harness.chip_mesh(1, allow_cpu=True)
    loop = harness.make_loop(cell, mesh, 2200001404)
    with mesh:
        loop.setup()
        module, m = scopes.hlo_scopes(scopes.step_hlo(loop), qwen3.SCOPES)
    assert module == f"jit_{kind}_step"
    # a prompt fills the prefill cache whole and XLA removes its write
    want = set(qwen3.SCOPES) - ({"kv_write"} if kind == "prefill" else set())
    assert want <= set(m.values()), sorted(want - set(m.values()))
    assert scopes.UNSCOPED in m.values()


def test_the_map_is_compiled_past_a_cached_step_without_scopes(tiny_root, tmp_path,
                                                               monkeypatch):
    """JAX's persistent cache keys a program without its metadata: where the
    step was first compiled and cached without scopes, the loop runs that
    executable, and the map has to come from a fresh compile."""
    import contextlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    cell = spec.load_cell("tiny.decode", tiny_root)
    mesh = harness.chip_mesh(1, allow_cpu=True)
    try:
        harness.place_compile_cache(tmp_path)
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
        with monkeypatch.context() as mp, mesh:
            mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
            harness.make_loop(cell, mesh, 1).setup()
        loop = harness.make_loop(cell, mesh, 1)
        with mesh:
            loop.setup()
            tokens = jax.ShapeDtypeStruct((loop.batch, 1), "int32",
                                          sharding=loop.tok_sharding)
            cached = loop.fn.lower(loop.params, {"tokens": tokens}, loop.cache).compile()
            fresh = scopes.step_hlo(loop)
        cached_map = scopes.hlo_scopes(cached.as_text(), qwen3.SCOPES)[1]
        assert set(cached_map.values()) == {scopes.UNSCOPED}
        assert "attn_core" in scopes.hlo_scopes(fresh, qwen3.SCOPES)[1].values()
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
