"""CPU tests of the per-phase reduction: the instruction -> phase map read
from a compiled module's text, and each phase's device time in a trace."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import scopes  # noqa: E402

HLO = """\
HloModule jit_step_flat, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(f32[8]{0} %param_0, f32[8]{0} %param_0), metadata={op_type="mul" op_name="jit(step_flat)/cada.eq3/mul" source_file="flat.py" source_line=3}
}

%wrapped_broadcast_computation (param_0.1: f32[]) -> f32[8] {
  %param_0.1 = f32[] parameter(0)
  ROOT %broadcast.2 = f32[8]{0} broadcast(f32[] %param_0.1), dimensions={}, metadata={op_type="broadcast_in_dim" op_name="jit(step_flat)/cada.gate/broadcast_in_dim"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.3 = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %p), index=1
  %slice_fusion.3 = f32[8]{0} fusion(f32[8]{0} %get-tuple-element.3), kind=kLoop, calls=%fused_computation, metadata={op_type="slice" op_name="jit(step_flat)/cada.eq3/jit(eq3_row_mean)/while/body/cada.pack/slice"}
  %get-tuple-element.4 = s32[] get-tuple-element((s32[], f32[8]{0}) %p), index=0
  %dynamic-update-slice.10 = f32[8]{0} dynamic-update-slice(f32[8]{0} %get-tuple-element.3, f32[1]{0} %get-tuple-element.3, s32[] %get-tuple-element.4)
  ROOT %tuple.5 = (s32[], f32[8]{0}) tuple(s32[] %get-tuple-element.4, f32[8]{0} %dynamic-update-slice.10)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="state.h"}
  %fusion.751 = f32[8]{0} fusion(f32[8]{0} %Arg_0.1), kind=kLoop, calls=%fused_computation, metadata={op_type="mul" op_name="jit(step_flat)/cada.eq3/mul"}
  %fused_amsgrad_flat.1 = (f32[8]{0}, f32[1,1]{1,0}) custom-call(f32[8]{0} %fusion.751), custom_call_target="tpu_custom_call", metadata={op_type="pallas_call" op_name="jit(step_flat)/cada.server_update/pallas_call"}
  %dot.5 = f32[8,8]{1,0} dot(f32[8,1]{1,0} %Arg_0.1, f32[1,8]{1,0} %Arg_0.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_type="dot_general" op_name="jit(step_flat)/vmap(transpose(jvp(cada.grad_eval)))/dot_general"}
  %add.6 = f32[8]{0} add(f32[8]{0} %Arg_0.1, f32[8]{0} %Arg_0.1), metadata={op_type="add" op_name="jit(step_flat)/add"}
  %copy.7 = f32[8]{0} copy(f32[8]{0} %fusion.751)
  %constant.8 = f32[] constant(0)
  %wrapped_broadcast = f32[8]{0} fusion(f32[] %constant.8), kind=kLoop, calls=%wrapped_broadcast_computation
  %while.8 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.2), condition=%cond, body=%body, metadata={op_type="while" op_name="jit(step_flat)/cada.eq3/jit(eq3_row_mean)/while"}
  %reshape.11 = f32[8]{0} reshape(f32[8]{0} %fusion.751), metadata={op_name="convert.57"}
  ROOT %multiply.9 = f32[8]{0} multiply(f32[8]{0} %copy.7, f32[8]{0} %add.6)
}
"""


@pytest.fixture(scope="module")
def op_map():
    return scopes.op_scopes(HLO)


@pytest.mark.parametrize("name, scope", [
    ("fusion.751", "cada.eq3"),                      # a fusion
    ("fused_amsgrad_flat.1", "cada.server_update"),  # a custom call
    ("dot.5", "cada.grad_eval"),                     # through vmap/jvp/transpose
    ("slice_fusion.3", "cada.pack"),                 # a while body op, nested
    ("while.8", "cada.eq3"),
    ("multiply.1", "cada.eq3"),                      # inside a fusion body
    ("add.6", None),                                 # named, in no phase
    ("copy.7", "cada.eq3"),                          # no metadata: its operand
    ("wrapped_broadcast", "cada.gate"),              # no metadata: its body
    ("multiply.9", "cada.eq3"),                      # no metadata: operands
    ("dynamic-update-slice.10", "cada.eq3"),         # no metadata: its while
    ("reshape.11", "cada.eq3"),                      # a compiler's name: operand
])
def test_op_scopes_innermost_phase(op_map, name, scope):
    assert op_map[name] == scope


def test_op_scopes_keeps_every_instruction(op_map):
    assert {"Arg_0.1", "param_0", "get-tuple-element.3", "tuple.5",
            "constant.8"} <= set(op_map)
    assert op_map["Arg_0.1"] is None   # named after an argument, no phase


def test_innermost_component():
    assert scopes.innermost("jit(s)/cada.eq3/jit(f)/while/body/cada.pack/"
                            "slice") == "cada.pack"
    assert scopes.innermost("jit(s)/vmap(transpose(jvp(cada.grad_eval)))/"
                            "dot_general") == "cada.grad_eval"
    assert scopes.innermost("jit(s)/add") is None


def _ev(name, start, dur, **stats):
    return [name, float(start), float(dur), stats]


TRACE = {
    "devices": {
        "/device:TPU:0": [
            _ev("fusion.751", 50, 100),                   # 100..150 inside
            _ev("while.8", 120, 60, op="while"),          # container: out
            _ev("slice_fusion.3", 130, 40, op="fusion"),
            _ev("add.6", 200, 10),                        # no phase
            _ev("fused_amsgrad_flat.1", 290, 20, op="custom-call"),
            _ev("unknown.1", 220, 5),                     # not in the map
        ],
        "/device:TPU:1": [
            _ev("fusion.751", 100, 30),
            _ev("dot.5", 140, 60),
        ],
    },
    "host": [_ev("bench.window", 100, 200), _ev("bench.prep", 120, 5)],
}


def test_scope_seconds_clips_skips_containers_and_averages(op_map):
    secs, unscoped = scopes.scope_seconds(TRACE, op_map)
    # eq3: dev0 50 (clipped from 100), dev1 30 -> mean 40 ns
    assert secs["cada.eq3"] == pytest.approx(40e-9)
    assert secs["cada.pack"] == pytest.approx(20e-9)           # 40 / 2
    assert secs["cada.grad_eval"] == pytest.approx(30e-9)      # 60 / 2
    assert secs["cada.server_update"] == pytest.approx(5e-9)   # 10 / 2
    assert unscoped == pytest.approx((10 + 5) / 2 * 1e-9)
    assert set(secs) == {"cada.eq3", "cada.pack", "cada.grad_eval",
                         "cada.server_update"}


class _View:
    """What a metric reader sees of a traced run."""

    def __init__(self, summary=True, steps=4):
        self.summary = type("Summary", (), {"busy_s": 1e-7})() \
            if summary else None
        self.trace = TRACE
        self.steps = steps


def test_phase_ms_per_step_and_none_cases(op_map, monkeypatch):
    built = []
    step_map = {}
    monkeypatch.setattr(scopes, "step_scopes",
                        lambda view: built.append(view) or step_map)
    step_map.update(op_map)
    view = _View()
    assert scopes.phase_ms(view, "cada.eq3") == pytest.approx(40e-9 / 4 * 1e3)
    assert scopes.phase_ms(view, "cada.rule_state") is None   # no op in it
    assert len(built) == 1                    # one rebuild serves every phase
    # a step compiled from code without the names reads nothing, not 0
    step_map.update({k: None for k in op_map})
    assert scopes.phase_ms(_View(), "cada.eq3") is None
    # no device trace (a CPU run): nothing, and no rebuild
    assert scopes.phase_ms(_View(summary=False), "cada.eq3") is None
    assert len(built) == 2


def test_abstract_takes_one_sharding_or_a_tree_of_them():
    jax = pytest.importorskip("jax")
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(jax.devices()[0])
    tree = {"a": jax.numpy.zeros((2, 3)), "b": (jax.numpy.ones(4, "int32"),
                                                None)}
    for shardings in (one, {"a": one, "b": (one, None)}):
        got = scopes._abstract(tree, shardings)
        assert jax.tree.structure(got) == jax.tree.structure(tree)
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
            assert (x.shape, x.dtype, x.sharding) == (y.shape, y.dtype, one)
