"""The split of a traced step's device time by the program's layer scopes
(bench/scopes.py) and the metric readers that read it, on hand-made HLO,
operations and traces; and the map of the tiny cells' real steps, compiled on
the CPU."""
import gzip
from pathlib import Path

import pytest

import bench_fixtures  # puts the checkout on sys.path
from bench_fixtures import tiny_root  # noqa: F401 (a fixture)
from bench import harness, scopes, spec
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
    assert scopes.scope_of(op_name) == scope


def test_hlo_scopes_maps_every_instruction():
    module, m = scopes.hlo_scopes(HLO)
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
        scopes.hlo_scopes("not HLO")


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
        module, m = scopes.hlo_scopes(f.read())
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
    _, m = scopes.hlo_scopes(HLO)
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


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_the_tiny_steps_map_to_every_scope(kind, tiny_root):
    """The map of the step the loop ran, fetched from its live state as a
    traced run does after the window."""
    cell = spec.load_cell(f"tiny.{kind}", tiny_root)
    mesh = harness.chip_mesh(1, allow_cpu=True)
    loop = harness.make_loop(cell, mesh, 2200001404)
    with mesh:
        loop.setup()
        module, m = scopes.hlo_scopes(scopes.step_hlo(loop))
    assert module == f"jit_{kind}_step"
    # a prompt fills the prefill cache whole and XLA removes its write
    want = set(scopes.SCOPES) - ({"kv_write"} if kind == "prefill" else set())
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
        assert set(scopes.hlo_scopes(cached.as_text())[1].values()) == {scopes.UNSCOPED}
        assert "attn_core" in scopes.hlo_scopes(fresh)[1].values()
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
