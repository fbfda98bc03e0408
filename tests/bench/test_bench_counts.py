"""The work the configurations count, against counts made by hand from
the published sizes."""
import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import manifest as M

SMOL = M.load_cell("smollm360m.agent-prefix")
TC = M.load_cell("tconst41m.stream-long")

# smollm-360m, one token through one layer: q, k, v = 2*960*(15+5+5)*64
# = 3,072,000; o = 2*15*64*960 = 1,843,200; SwiGLU = 3*2*960*2560 =
# 14,745,600; together 19,660,800, plus 4*15*64 = 3,840 per key attended.
# Output head 2*960*49152 = 94,371,840.
SMOL_TOKEN = 19_660_800
SMOL_HEAD = 94_371_840


def test_smollm_decode_flops_by_hand():
    one = 32 * (SMOL_TOKEN + 3_840 * 1) + SMOL_HEAD
    assert one == 723_640_320
    assert SMOL.model.decode_flops(SMOL.config, np.array([0])) == one
    two = 32 * (SMOL_TOKEN + 3_840 * 1000) + SMOL_HEAD
    assert SMOL.model.decode_flops(SMOL.config, np.array([0, 999])) == \
        one + two


def test_smollm_admit_flops_counts_forwarded_tail_only():
    # 76 forwarded positions 1024..1099, attending 1025..1100 keys
    per_layer = 76 * SMOL_TOKEN + 3_840 * sum(range(1025, 1101))
    assert per_layer == 1_804_300_800
    want = 32 * per_layer + SMOL_HEAD
    assert SMOL.model.admit_flops(SMOL.config, 1100, 1024) == want
    assert SMOL.model.admit_flops(SMOL.config, 1100, 0) > want


def test_smollm_paged_kernel_work_by_hand():
    # a step feeding position 127 reads 128 live keys and values:
    # 2 * 128 * 5 * 64 * 2 bytes = 163,840; query and output 2 * 15 * 64
    # * 2 = 3,840; 2 page-table entries of 4 bytes; 32 layers
    nbytes, flops = SMOL.model.kernel_work(
        SMOL.config, "paged_decode_attention", np.array([127]))
    assert nbytes == 32 * (163_840 + 3_840 + 8) == 5_366_016
    assert flops == 32 * 4 * 15 * 64 * 128 == 15_728_640
    assert SMOL.model.kernel_work(SMOL.config, "flash_attention_fwd",
                                  np.array([1])) is None


# tconst-41m, one token through one layer: q, k, v = 2*432*(12+12+12)*36
# = 1,119,744; o = 2*432*432 = 373,248; SwiGLU = 6*432*1728 = 4,478,976;
# together 5,971,968; 4*12*36 = 1,728 per (query, key) pair.  Head
# 2*432*50257 = 43,422,048.  2 blocks of 4 layers, W_oh = W_og = 256.
TC_TOKEN = 5_971_968
TC_HEAD = 43_422_048


def test_tconst_hit_step_by_hand():
    # position 300: window offset 44 (45 window keys), 256 context slots
    # attended by the 3 layers after the first, in each of 2 blocks
    block = 4 * (TC_TOKEN + 1_728 * 45) + 3 * 1_728 * 256
    assert 2 * block + TC_HEAD == 94_474_080
    assert TC.model.decode_flops(TC.config, np.array([300])) == 94_474_080


def test_tconst_resync_by_hand():
    # synchronisation over N = 512 history positions, tail of 256
    compress = (2 * 432 * 432 * 256          # tail queries
                + 2 * 432 * 864 * 512        # keys and values of history
                + 1_728 * (256 * 512 - 256 * 255 // 2)   # causal pairs
                + 2 * 432 * 432 * 256        # output projection
                + 6 * 432 * 1728 * 256)      # SwiGLU of the tail
    assert compress == 1_890_017_280
    tail = 2 * (TC_TOKEN * 256 + 1_728 * 256 * 257 // 2)
    ctx_kv = 2 * 432 * 864 * 256
    restore = (2 * 432 * 432 * 512 + 1_728 * 256 * 257 // 2 +
               2 * 432 * 432 * 512 + 6 * 432 * 1728 * 512)
    # two blocks of compress + tail + cached C_h keys/values; one restore
    # (the last block's feeds nothing)
    want = 2 * (compress + tail + ctx_kv) + restore
    assert want == 13_237_198_848
    assert TC.model.resync_flops(TC.config, np.array([512]))[0] == want
    # a step at a window boundary pays the resync; at position 0, none
    step = 2 * (4 * (TC_TOKEN + 1_728) + 3 * 1_728 * 256) + TC_HEAD
    assert TC.model.decode_flops(TC.config, np.array([512])) == \
        pytest.approx(want + step)
    assert TC.model.resync_flops(TC.config, np.array([0]))[0] == 0


def test_tconst_admit_flops_by_hand():
    # prompt of 300: history 256 synchronised, window of 44 tokens with
    # causal self-attention and 256 context slots
    win = 2 * (4 * (TC_TOKEN * 44 + 1_728 * 44 * 45 // 2) +
               3 * 1_728 * 44 * 256)
    want = TC.model.resync_flops(TC.config, np.array([256]))[0] + win + \
        TC_HEAD
    assert TC.model.admit_flops(TC.config, 300, 0) == pytest.approx(want)
    assert TC.model.kernel_work(TC.config, "paged_decode_attention",
                                np.array([1])) is None
