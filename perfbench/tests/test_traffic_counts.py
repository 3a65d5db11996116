"""The generator and the count functions, against hand-computed values."""
from __future__ import annotations

import json

import numpy as np
import pytest

from conftest import BENCH
from lib import counts, traffic

MIX = json.loads((BENCH / "traffic" / "decode_mix.json").read_text())


def test_cycles_same_sizes_every_seed_and_prompts_per_seed():
    a = [traffic.lm_cycle(MIX, 2 ** 31 + 77, c) for c in range(5)]
    b = [traffic.lm_cycle(MIX, 2 ** 31 + 77, c) for c in range(5)]
    other = [traffic.lm_cycle(MIX, 5, c) for c in range(5)]
    assert a == b
    strata = traffic.gen_strata(MIX["gen"])
    # every cycle of every seed holds each stratum once
    for cycle in a + other:
        assert sorted(j["gen"] for j in cycle) == strata
    # the seed draws the order and the prompts
    assert [[j["gen"] for j in c] for c in a] != [[j["gen"] for j in c]
                                                  for c in other]
    seeds = [j["seed"] for c in a for j in c]
    assert len(set(seeds)) == len(seeds)
    assert seeds != [j["seed"] for c in other for j in c]
    assert all(0 <= s < 2 ** 32 for s in seeds)


def test_strata_are_the_exponential_stratum_means():
    gen = MIX["gen"]
    assert gen == {"dist": "exponential", "mean": 337.99, "strata": 4}
    # by hand, mean m = 337.99, quartiles x = m ln(4/3), m ln 2, m ln 4:
    # below x1: m - 3 x1; x1..x2: m + (0.75 x1 - 0.5 x2) / 0.25;
    # x2..x3: m + (0.5 x2 - 0.25 x3) / 0.25 = m; above x3: x3 + m
    m = 337.99
    x1, x2, x3 = m * np.log(4 / 3), m * np.log(2), m * np.log(4)
    want = [m - 3 * x1, m + (0.75 * x1 - 0.5 * x2) / 0.25, m, x3 + m]
    assert traffic.gen_strata(gen) == [round(w) for w in want]
    assert traffic.gen_strata(gen) == [46, 161, 338, 807]
    # the cycle's mean is the distribution's
    assert sum(want) / 4 == pytest.approx(m)
    assert max(traffic.gen_strata(gen)) <= MIX["max_new_tokens"]
    with pytest.raises(ValueError):
        traffic.gen_strata(dict(gen, dist="pareto"))


def test_prompt_copy_matches_the_systems_prompts():
    from repro.configs import get_config
    from repro.data.pipeline import batch_for

    cfg = get_config("qwen25_3b").smoke()
    for seed in (0, 7, 4_000_000_000):
        want = batch_for(cfg, 3, 11, seed=seed)["tokens"]
        got = traffic.prompt_tokens(cfg.vocab, 3, 11, seed)
        np.testing.assert_array_equal(got, want)


SMALL = {"hidden_size": 4, "intermediate_size": 8, "num_hidden_layers": 2,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
         "vocab_size": 10}


def test_lm_counts_by_hand():
    # one layer: q 4*2*2 + o 2*2*4 + k, v 4*1*2 each + gated MLP 3*4*8
    assert counts.layer_params(SMALL) == 32 + 16 + 96
    # prefill, 1 x 3 tokens: 2*144*2 layers*3 tokens; causal keys 1+2+3=6,
    # 4 * 2 layers * 2 heads * 2 dims * 6; head 2*1*4*10 at the last token
    assert counts.prefill_flops(SMALL, 1, 3) == 1728 + 192 + 80
    # decode at position 3 (4 keys): 2*(144*2 + 40) + 4*2*2*2*4
    assert counts.decode_flops(SMALL, 1, 3) == 656 + 128
    # k and v, 2 layers, 4 positions, 1 head of 2, 2 bytes
    assert counts.kv_bytes(SMALL, 1, 3) == 2 * 2 * 4 * 1 * 2 * 2


def test_pricing_counts_by_hand():
    assert counts.MC_OPS_PER_PATH_STEP == {"black-scholes": 140,
                                           "heston": 157}
    assert counts.mc_ops("heston", 10, 5) == 157 * 50


def test_qwen_counts_at_published_widths():
    cfg = json.loads((BENCH / "configs" / "qwen25_3b.json").read_text())
    # 2048*16*128*2 + 2048*2*128*2 + 3*2048*11008 per layer, 2.77 B in all
    assert counts.layer_params(cfg) == 77_070_336
    assert counts.prefill_flops(cfg, 32, 512) == pytest.approx(9.218e13,
                                                               rel=1e-3)
