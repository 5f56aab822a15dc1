"""The plain reference against the program served in float32 at a tiny
size, for both architectures the cells use (llama-style RMSNorm with
grouped KV heads, and OLMo's non-parametric LayerNorm with full heads).
In float32 the served greedy tokens must be the reference's own best."""
import copy
import json

import pytest

import tiny
from bench import check, loadgen, system as S
from bench.driver import drive

OLMO = {"hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 4, "max_position_embeddings": 128,
        "layer_norm_eps": 1e-05, "rope_theta": 10000.0,
        "tie_word_embeddings": True, "vocab_size": 256}


@pytest.mark.parametrize("registry", ["smollm-360m", "olmo-1b"])
def test_float32_program_matches_reference(registry):
    cfile = copy.deepcopy(tiny.TINY_CONFIG)
    cfile["registry"] = registry
    cfile["serve"]["dtype"] = "float32"
    if registry == "olmo-1b":
        cfile["config"] = OLMO
    system = S.build(cfile, 3)
    assert system.cfg.norm_type == ("rmsnorm" if registry == "smollm-360m"
                                    else "nonparametric")
    sched = system.scheduler()
    S.warm_up(system, sched, True)
    mix = json.loads(json.dumps(tiny.TINY_CHAT))
    plan = loadgen.poisson_plan(mix, 3.0, 3.0, 3, 2, 256)
    win = drive(system, sched, mix, 3.0, plan=plan)
    picked = check.sample(win.served, 6, 3)
    assert {s.spec.task for s in picked} == {0, 1}
    assert max(len(s.spec.prompt) for s in picked) > 32   # several chunks
    gaps = check.gaps(system.params, system.table, cfile, picked,
                      mix["output_len"]["max"])
    assert max(gaps) < 1e-4, gaps
