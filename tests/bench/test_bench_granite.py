"""The granite-4.0-h-micro configuration and its cells: the file resolves
to the program's model with the published sizes, the work it counts
matches counts made by hand, and the check passes a sound tiny run while
the fp8 control fails it; the new smollm control cell runs with its
tails reported."""
import copy
import json
import time

import jax
import numpy as np
import pytest

import tiny
import cell as C
import manifest as M

GRANITE = "granite4hmicro.stream-hybrid"
# this test's own limits, for the tiny float32 sizes, where the program
# reads 0 from the reference; the fp8 control reads 0.02-0.2 on smollm
# and 0.0021-0.0028 on granite (logits divided by 8, embedding at
# 0.02 / 12; measured on the CPU)
LIMIT = 0.01
LIMIT_GRANITE = 0.001

TINY_GRANITE = dict(vocab_size=512, d_model=64, n_heads=4, n_kv_heads=2,
                    head_dim=16, d_ff=128, ssm_state=16, ssm_heads=4,
                    ssm_head_dim=16, ssm_expand=1, ssm_chunk=8)
TINY_STREAM = dict(slots=4, max_len=96, pool_pages=48, clients=4,
                   backlog=16, prompt_mix=[[1.0, 10, 20]],
                   output_mix=[[1.0, 20, 40]],
                   check={"requests": 2, "logit_gap_limit": LIMIT_GRANITE})
TINY_SHORT = dict(slots=4, max_len=32, pool_pages=16, rate=20.0,
                  prompt_mix=[[0.7, 4, 8], [0.3, 9, 12]],
                  output_mix=[[1.0, 4, 12]],
                  check={"requests": 4, "logit_gap_limit": LIMIT})


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(C.M, "load_peaks", lambda kind, root=None:
                        tiny.CPU_PEAKS)


def _tiny(name, model, traffic, serving):
    cell = M.load_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(model, dtype="float32")
    cell.config["serving"].update(serving)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic.update(traffic)
    return cell


def test_file_holds_the_published_sizes_under_both_names():
    cell = M.load_cell(GRANITE)
    c, m = cell.config, cell.config["model"]
    man = {x["name"]: x for x in M.load_manifest()["configs"]}
    assert man[cell.config_name]["reduced"] == ["num_hidden_layers"]
    assert c["num_hidden_layers"] == m["n_layers"] == 10
    assert m["layer_pattern"] == c["layer_types"][:10]
    assert c["layer_types"].count("attention") == 4 and \
        len(c["layer_types"]) == 40
    pairs = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "shared_intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "mamba_d_state": "ssm_state", "mamba_n_heads": "ssm_heads",
             "mamba_d_head": "ssm_head_dim", "mamba_expand": "ssm_expand",
             "mamba_d_conv": "ssm_conv", "mamba_chunk_size": "ssm_chunk",
             "rms_norm_eps": "norm_eps",
             "attention_multiplier": "attention_multiplier",
             "embedding_multiplier": "embedding_multiplier",
             "residual_multiplier": "residual_multiplier",
             "logits_scaling": "logits_scaling",
             "tie_word_embeddings": "tie_embeddings"}
    for pub, ours in pairs.items():
        assert c[pub] == m[ours], pub
    assert c["mamba_n_groups"] == 1 and c["num_local_experts"] == 0
    assert c["position_embedding_type"] == "nope" and \
        m["position_embedding"] == "nope"
    assert m["head_dim"] * m["n_heads"] == c["hidden_size"]
    assert c["mamba_n_heads"] * c["mamba_d_head"] == \
        c["mamba_expand"] * c["hidden_size"]
    # the program's registered config is the published model whole
    from repro.config import get_config
    full = get_config(c["arch"])
    assert full.n_layers == 40 and \
        list(full.layer_pattern) == c["layer_types"]


def test_config_resolves_and_weights_have_the_program_tree():
    cell = M.load_cell(GRANITE)
    cfg = C.model_config(cell)
    assert isinstance(cfg.layer_pattern, tuple) and cfg.n_layers == 10
    from repro.models.api import build_model
    key = jax.random.PRNGKey(0)
    want = jax.eval_shape(build_model(cfg).init, key)
    got = jax.eval_shape(lambda k: cell.model.make_weights(k, cell.config),
                         key)
    assert jax.tree_util.tree_structure(want) == \
        jax.tree_util.tree_structure(got)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(got))
    assert n == 951_991_232        # 0.95B parameters, 3.81 GB in float32
    small = _tiny(GRANITE, TINY_GRANITE, TINY_STREAM, {})
    C.check_tree(C.model_config(small), C.make_weights(small, 3))


# granite-4.0-h-micro, one token (published widths).  A Mamba-2 layer:
# in_proj 2*2048*8512 = 34,865,152; conv 2*4*4352 = 34,816; state
# update and read-out 4*64*64*128 = 2,097,152; out_proj 2*4096*2048 =
# 16,777,216; SwiGLU 6*2048*8192 = 100,663,296; together 154,437,632.
# The attention layer: q, k, v 2*2048*48*64 = 12,582,912; o 2*32*64*2048
# = 8,388,608; SwiGLU; together 121,634,816, plus 4*32*64 = 8,192 per
# key attended.  Head 2*2048*100352 = 411,041,792.  9 + 1 layers.
MAMBA_TOKEN = 154_437_632
ATTN_TOKEN = 121_634_816
HEAD = 411_041_792


def test_decode_and_admit_flops_by_hand():
    cell = M.load_cell(GRANITE)
    mod, c = cell.model, cell.config
    one = 9 * MAMBA_TOKEN + ATTN_TOKEN + 8_192 * 1 + HEAD
    assert one == 1_922_623_488
    assert mod.decode_flops(c, np.array([0])) == one
    thousand = 9 * MAMBA_TOKEN + ATTN_TOKEN + 8_192 * 1000 + HEAD
    assert mod.decode_flops(c, np.array([0, 999])) == one + thousand
    # a 13-token prompt, forwarded whole (positions 0..12 attend 1..13)
    admit = 13 * (9 * MAMBA_TOKEN + ATTN_TOKEN) + 8_192 * 91 + HEAD
    assert admit == 20_062_242_816
    assert mod.admit_flops(c, 13, 0) == admit


def test_paged_kernel_work_by_hand():
    cell = M.load_cell(GRANITE)
    # a step feeding position 127 reads 128 live keys and values of the
    # one attention layer: 2 * 128 * 8 * 64 * 2 bytes = 262,144; query
    # and output 2 * 32 * 64 * 2 = 8,192; 2 page-table entries of 4 bytes
    nbytes, flops = cell.model.kernel_work(
        cell.config, "paged_decode_attention", np.array([127]))
    assert nbytes == 262_144 + 8_192 + 8 == 270_344
    assert flops == 4 * 32 * 64 * 128 == 1_048_576
    assert cell.model.kernel_work(cell.config, "flash_attention_fwd",
                                  np.array([1])) is None


def test_traffic_fits_and_pool_holds_every_longest_stream():
    cell = M.load_cell(GRANITE)
    t, page = cell.traffic, cell.config["serving"]["page_size"]
    longest = t["prompt_mix"][0][2] + t["output_mix"][0][2] + \
        cell.config["serving"]["chunk"]
    assert longest <= t["max_len"]
    assert t["pool_pages"] == t["slots"] * -(-longest // page)
    assert cell.config["serving"]["prefill_chunk"] == \
        cell.config["model"]["ssm_chunk"]
    assert not cell.config["serving"]["prefix_sharing"]


def test_tiny_granite_run_passes_and_control_fails():
    cell = _tiny(GRANITE, TINY_GRANITE, TINY_STREAM,
                 dict(chunk=4, prefill_chunk=8, page_size=8))
    out = C.run(cell, 2 ** 31 + 5, 2.0, False, time.perf_counter(),
                log=lambda s: None, control=True)
    ch = out["checks"]
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert ch["logit_gap"]["value"] <= LIMIT_GRANITE
    ctl = out["control"]
    assert not ctl["correct"]
    assert ctl["checks"]["logit_gap"]["value"] > LIMIT_GRANITE
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}


def test_tiny_smollm_short_poisson_reports_its_tails():
    cell = _tiny("smollm360m.short-poisson", tiny.TINY_MODEL["smollm-360m"],
                 TINY_SHORT, tiny.TINY_SERVING["smollm-360m"])
    out = C.run(cell, 23, 2.0, False, time.perf_counter(),
                log=lambda s: None)
    assert out["correct"]
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert out["attempted"] == round(TINY_SHORT["rate"] * 2.0)


def test_short_poisson_lm_traffic_is_the_tconst_mix_unshared():
    lm = json.load(open(f"{M.BENCH_DIR}/traffic/short-poisson-lm.json"))
    tc = json.load(open(f"{M.BENCH_DIR}/traffic/short-poisson.json"))
    for k in ("kind", "slots", "max_len", "prompt_mix", "output_mix"):
        assert lm[k] == tc[k], k
    assert "n_prefixes" not in lm and lm["pool_pages"] == 64 * 4
