"""FLOPs and needed bytes, against numbers worked out by hand for the two
configurations at a stated batch and context."""
import json
from pathlib import Path

import counts
import harness

HERE = Path(__file__).resolve().parent


def dims(name):
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    return harness.arch_of(cfg).dims(cfg)


# h2o-danube-1.8b (huggingface.co/h2oai/h2o-danube-1.8b-base), at full size
DANUBE = counts.Dims(layers=24, d_model=2560, heads=32, kv_heads=8, head_dim=80, d_ff=6912,
                     vocab=32000, window=4096)


def test_danube_by_hand():
    m = DANUBE
    # per layer: 2560*(32+16)*80 + 32*80*2560 + 3*2560*6912 = 69,468,160 matmul weights
    assert m.layer_matmul_params == 69_468_160 and m.head_dim == 80 and m.window == 4096
    # prefill of 1000 true tokens: 2*1000*24*69,468,160 + 4*32*80*24*(1000*1001/2) + 2*2560*32000
    assert counts.prefill_flops(m, 1000) == 3_334_471_680_000 + 123_002_880_000 + 163_840_000
    # decode, two slots attending to 100 and 2000 positions
    assert counts.decode_flops(m, [100, 2000]) == 2 * 3_498_311_680 + 245_760 * 2100
    # weights: 2 B * (24*69,468,160 + 2560*32000 + 49 norms of 2560) = 3,498,562,560
    assert counts.weight_bytes(m) == 3_498_562_560
    # four data-parallel groups read their weights; 61,440 B of K and V per position
    assert counts.decode_bytes(m, [100, 2000], groups=4) == (
        4 * 3_498_562_560 + 61_440 * 2100 + 2 * 2560 * 2)


def test_yi_stage_by_hand():
    m = dims("yi-34b-stage8")
    # 7168*(56+16)*128 + 56*128*7168 + 3*7168*20480 = 557,842,432
    assert m.layer_matmul_params == 557_842_432 and m.head_dim == 128 and m.window is None
    assert counts.prefill_flops(m, 512) == 4_569_845_202_944 + 30_123_491_328 + 917_504_000
    # sixteen slots at 1024 positions each
    per_slot = 2 * (8 * 557_842_432 + 7168 * 64000) + 4 * 56 * 128 * 8 * 1024
    assert counts.decode_flops(m, [1024] * 16) == 16 * per_slot == 161_245_822_976
    assert counts.weight_bytes(m) == 9_843_226_624
    assert counts.decode_bytes(m, [1024] * 16) == 9_843_226_624 + 32_768 * 16 * 1024 + 16 * 7168 * 2


def test_padding_and_window_are_not_counted():
    m = DANUBE
    short = counts.Dims(**{**m.__dict__, "window": 16})
    assert counts.decode_bytes(short, [2000]) == counts.decode_bytes(short, [16])
    assert counts.prefill_flops(m, 300) < counts.prefill_flops(m, 512)
