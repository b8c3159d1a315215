"""Roofline extraction: collective-bytes HLO parsing + term arithmetic."""
import numpy as np

from repro.roofline.analysis import (HBM_BW, ICI_BW, PEAK_FLOPS,
                                     collective_breakdown,
                                     collective_bytes_from_hlo,
                                     roofline_terms)

HLO = """
HloModule test
  %all-reduce.5 = bf16[16,512]{1,0} all-reduce(bf16[16,512]{1,0} %x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = f32[64,128]{1,0} all-gather(%y), replica_groups=[2,8]<=[16], dimensions={0}
  %rs = f32[8,128]{1,0} reduce-scatter(%z), replica_groups=[2,8]<=[16], to_apply=%add
  %a2a = bf16[32,32]{1,0} all-to-all(%w), replica_groups={{0,1}}
  %cp = u32[4]{0} collective-permute(%v), source_target_pairs={{0,1}}
  %ard = bf16[16,512]{1,0} all-reduce-done(%start)
  %ags = (f32[4,4]{1,0}, f32[16,4]{1,0}) all-gather-start(%q), replica_groups=[4,4]<=[16], dimensions={0}
"""


def test_collective_bytes_parsing():
    b = collective_breakdown(HLO)
    # all-reduce: result 16*512*2 = 16384 bytes (operand == result)
    assert b["bytes"]["all-reduce"] == 16 * 512 * 2
    # all-gather: result 64*128*4; operand = result / group(8);
    # the async start tuple contributes its operand entry f32[4,4] directly
    assert b["bytes"]["all-gather"] == (64 * 128 * 4) // 8 + 4 * 4 * 4
    # reduce-scatter: operand = result * group(8)
    assert b["bytes"]["reduce-scatter"] == 8 * 128 * 4 * 8
    assert b["bytes"]["all-to-all"] == 32 * 32 * 2
    assert b["bytes"]["collective-permute"] == 4 * 4
    # -done skipped; -start tuple handled (halved), counted under all-gather
    assert b["counts"]["all-reduce"] == 1
    total = collective_bytes_from_hlo(HLO)
    assert total == sum(b["bytes"].values())


def test_collective_parsing_tpu_tiled_layouts():
    """Compiled TPU HLO prints tiled layouts (`{1,0:T(8,128)S(1)}`) on every
    result; a collective fused into a `calls=%all-reduce-scatter` fusion is
    counted by its inner all-reduce, the fusion line itself not at all."""
    hlo = (
        "  %all-reduce.2 = f32[164352,64]{1,0:T(8,128)} all-reduce(%pad.44), "
        "replica_groups={{0,1,2,3}}, to_apply=%add\n"
        "  %fusion.40 = f32[41088,64]{1,0:T(8,128)S(1)} fusion(%b), "
        "kind=kCustom, calls=%all-reduce-scatter\n"
        "  %all_to_all.3 = f32[4,609,64]{1,2,0:T(8,128)S(1)} all-to-all("
        "%copy.156), replica_groups={{0,1,2,3}}, dimensions={0}\n"
        "  %slice = f32[1,64]{1,0:T(1,128)S(1)} fusion(%all-reduce.2, %i)\n")
    b = collective_breakdown(hlo)
    assert b["counts"] == {"all-reduce": 1, "all-to-all": 1}
    assert b["bytes"]["all-reduce"] == 164352 * 64 * 4
    assert b["bytes"]["all-to-all"] == 4 * 609 * 64 * 4


def test_roofline_terms_arithmetic():
    from repro.configs.base import SHAPES, get_config

    cfg = get_config("llama3_2_1b")
    rec = {"flops": PEAK_FLOPS, "bytes_accessed": HBM_BW,
           "collective_bytes": ICI_BW * 2}
    out = roofline_terms(rec, cfg, SHAPES["train_4k"], 256)
    assert abs(out["compute_s"] - 1.0) < 1e-9
    assert abs(out["memory_s"] - 1.0) < 1e-9
    assert abs(out["collective_s"] - 2.0) < 1e-9
    assert out["dominant"] == "collective"
    assert out["roofline_bound_s"] == 2.0
    assert 0 < out["useful_flops_ratio"] < 10


def test_model_flops_sanity():
    from repro.configs.base import SHAPES, get_config
    from repro.roofline.analysis import model_flops

    cfg = get_config("llama3_2_1b")
    train = model_flops(cfg, SHAPES["train_4k"])
    prefill = model_flops(cfg, SHAPES["prefill_32k"])
    decode = model_flops(cfg, SHAPES["decode_32k"])
    assert train > prefill > decode > 0
    # train ~ 6/2 x prefill adjusted for batch/seq: just sanity bounds
    assert decode < 1e-3 * prefill
    # MoE active < total
    v3 = get_config("deepseek_v3_671b")
    from repro.roofline.analysis import count_params
    total, active = count_params(v3)
    assert active < 0.15 * total  # 37B activated of 671B
