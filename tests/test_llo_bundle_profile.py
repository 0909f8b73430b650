"""scripts/llo_bundle_profile.py: the static profile of a kernel's loop body
from the compiler's final bundles, on a dump written by hand (the real ones
come from a compile for a described v5e: the script's docstring)."""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "llo_bundle_profile", os.path.join(REPO, "scripts",
                                       "llo_bundle_profile.py"))
lp = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(lp)

HEAD = """= control target key start
LH: loop header
= control target key end

     0   :  { %s1_s0 = inlined_call_operand.hbm [shape: u8[8,128]] /* a comment with vsel in it */ }
   0x1   :  { %9 = vsyncpa [#allocation3], 0 }
   0x2 LB: > { %s2_s1 = sadd.s32 1, %s2_s1 }
"""
TAIL = """  0x90 PF: > { %s20_s25 = sadd.s32 1, %s74038_s25 }
  0x91   :  { %67534 = vsyncpa [#allocation3], 1 }
"""


def _body(n_matmul_bundles: int, n_mux_bundles: int) -> str:
    lines, at = [], 3
    for _ in range(n_matmul_bundles):
        lines.append(
            f"  {at:#x}   : >> {{ %1 = vmatmul.bf16.gmra.mxu1 %v1_v40  ;;  "
            "%v2_v40 = vld [vmem:[#allocation1168_spill] sm:$0xff]  ;;  "
            "%v3_v1 = vpop.f32.mrf.mxu0  ;;  %v4_v2 = vadd.f32 %v3_v1, %v2_v40 }")
        at += 1
    for _ in range(n_mux_bundles):
        lines.append(
            f"  {at:#x}   : >> {{ %v5_v3 = vsel %vm1_vm0, %v1_v1, %v2_v2  ;;  "
            "%v6_v4 = vsel %vm1_vm0, %v3_v1, %v4_v2  ;;  "
            "%7 = vst [vmem:[#allocation9_spill] sm:$0xff] %v5_v3 }")
        at += 1
    lines.append(f"  {at:#x}   :  {{}}")           # an empty bundle inside
    return "\n".join(lines) + "\n"


@pytest.fixture
def dump(tmp_path):
    def write(n_matmul, n_mux, name="1-ddt_predict_traverse_oblivious.1-71"):
        path = tmp_path / f"{name}-final_bundles.txt"
        path.write_text(HEAD + _body(n_matmul, n_mux) + TAIL)
        (tmp_path / f"{name[:-3]}-70-schedule-analysis_final_bundles.txt"
         ).write_text("Schedule analysis:\n")
        return str(tmp_path)
    return write


def test_the_body_is_the_deepest_loop_and_its_opcodes_are_counted(dump):
    p = lp.profile(lp.find_dump(dump(64, 32), "oblivious"))
    assert p["depth"] == 2 and p["bundles"] == 96
    assert (p["vmatmul"], p["vsel"], p["vld"], p["vst"], p["vpop"],
            p["vadd"]) == (64, 64, 64, 32, 64, 64)
    assert p["spill_refs"] == 96            # a fill a matmul bundle, a spill
    assert p["mxu_cycles"] == 64 * 16 // 4  # a mux bundle


def test_the_window_model_charges_a_stretch_without_matmuls(dump):
    """64 bundles that hold a matmul each keep the four MXUs busy for 256
    cycles; 32 bundles of selects after them add their own length: the
    MXUs wait. Spread evenly the same instructions cost the MXU's time
    alone."""
    body = lp.loop_body(lp.parse_bundles(lp.find_dump(dump(64, 32),
                                                      "oblivious")), None)[1]
    assert lp.window_model(body, 32) == (256 + 32, 32)
    assert lp.window_model(body, 96) == (256, 0)      # one window hides it
    p = lp.profile(lp.find_dump(dump(64, 32), "oblivious"), window=32)
    assert (p["model_cycles"], p["mxu_idle_cycles"]) == (288, 32)


def test_the_tail_after_the_last_matmul_can_be_cut(dump):
    p = lp.profile(lp.find_dump(dump(64, 32), "oblivious"), window=32,
                   cut_tail=True)
    assert (p["bundles"], p["tail_cut"], p["vsel"]) == (64, 32, 0)
    assert (p["model_cycles"], p["mxu_idle_cycles"]) == (256, 0)


@pytest.mark.parametrize("depth,bundles", [(1, 98), (2, 96)])
def test_another_nesting_can_be_asked_for(dump, depth, bundles):
    p = lp.profile(lp.find_dump(dump(64, 32), "oblivious"), depth=depth)
    assert p["bundles"] == bundles


def test_the_command_line_prints_a_table_and_json(dump, capsys):
    d = dump(64, 32)
    assert lp.main([d, "--kernel", "oblivious", "--table", "50"]) == 0
    said = capsys.readouterr().out
    assert "loop body at depth 2" in said and "vmatmul 64" in said
    assert said.splitlines()[-2].split()[:2] == ["0", "50"]
    assert lp.main([d, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["table"][0]["vmatmul"] == 64
    with pytest.raises(SystemExit, match="final_bundles"):
        lp.find_dump(d, "no_such_kernel")
