"""A tiny cell for the CPU rehearsals: the harness's whole control flow
(weights and data from the seed, the program's rounds, the window, the
trace, the reference and the verdict) at a size a test run holds."""
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

TINY_CONFIG = {
    "name": "tiny", "family": "dense", "program_arch": "qwen2-0.5b",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "attention_bias": True,
    "program": {"remat": True, "logit_chunk": 0, "attn_chunk": 32}}

# set from sound tiny runs (about 0.01, 0.01 and 0.06) with room below
# what the faults read (0.3 and up)
TINY_LIMITS = {"update_gap": 0.1, "change_gap": 0.1, "update_diff": 0.3}


@pytest.fixture
def tiny_cell():
    from perfbench import harness

    def make(compression="none"):
        with open(os.path.join(HERE, "traffic", "fedavg-c4.json")) as f:
            traffic = json.load(f)
        traffic.update(clients=6, clients_per_round=4, batches_per_client=2,
                       batch_size=2, seq_len=32, compression=compression,
                       topk_fraction=0.01)
        with open(os.path.join(os.path.dirname(HERE),
                               "BENCHMARK.json")) as f:
            bench = json.load(f)
        return harness.Cell(name="tiny", chips=1, config=dict(TINY_CONFIG),
                            traffic=traffic, limits=dict(TINY_LIMITS),
                            end_to_end=bench["end_to_end"],
                            per_layer=bench["per_layer"])

    return make
