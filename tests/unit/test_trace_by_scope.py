"""`scripts/trace_by_scope.py`: a traced program's operations summed by the
named scope their instructions carry in the compiled text."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TEXT = '''
%fused_computation.1 (p: s32[64]) -> s32[64] {
  %add.9 = s32[64]{0} add(%p, %p), metadata={op_name="jit(f)/while/body/moe_ffn/moe_experts/add"}
}
%body (arg: (s32[], bf16[640,512])) -> (s32[], bf16[640,512]) {
  %fusion.7 = s32[64]{0:T(128)} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/moe_ffn/moe_experts/jit(_where)/select_n"}
  %gmm.3 = bf16[640,512]{1,0:T(8,128)(2,1)} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/moe_ffn/moe_experts/jit(gmm)/pallas_call"}
  %_gdn_update_pallas.1 = (f32[64,32,128]{2,1,0}, f32[12,64]{1,0}) custom-call(%s), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/gdn_update/jit(_gdn_update_pallas)/pallas_call"}
  %sort.2 = f32[64,512]{1,0} sort(%l), dimensions={1}, metadata={op_name="jit(f)/while/body/moe_ffn/moe_router/top_k"}
  %reduce-window.5 = s32[9,128]{1,0} reduce-window(%c, %z), window={size=1x128}
}
'''


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "trace_by_scope", os.path.join(ROOT, "scripts", "trace_by_scope.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_an_instruction_takes_its_innermost_scope_and_its_opcode(tool):
    names = tool.scopes_from_text(TEXT)
    assert names["fusion.7"] == ("moe_experts", "fusion")
    assert names["gmm.3"] == ("moe_experts", "custom-call")
    assert names["_gdn_update_pallas.1"] == ("gdn_update", "custom-call")  # a tuple's shape
    assert names["sort.2"] == ("moe_router", "sort")
    assert names["reduce-window.5"] == ("rest", "reduce-window")  # no op_name


def test_a_programs_operations_are_summed_by_scope_with_kernels_apart(tool):
    program = {"count": 2, "total_s": 1.0, "ops": {
        "fusion.7 s32[64]": {"count": 8, "total_s": 0.008},
        "gmm.3 bf16[640,512]": {"count": 8, "total_s": 0.8},
        "sort.2 f32[64,512]": {"count": 8, "total_s": 0.08},
        "reduce-window.5 s32[9,128]": {"count": 8, "total_s": 0.016},
        "fusion.99 f32[1]": {"count": 1, "total_s": 0.001},
    }}
    table = tool.by_scope(program, tool.scopes_from_text(TEXT))
    assert table["moe_experts"]["count"] == 8
    assert table["moe_experts"]["total_s"] == pytest.approx(0.008)
    assert table["moe_experts:kernel"]["total_s"] == pytest.approx(0.8)
    assert table["moe_router"]["total_s"] == pytest.approx(0.08)
    assert table["rest"]["total_s"] == pytest.approx(0.016)
    assert table["unmapped"]["count"] == 1  # a name the text does not hold
