"""Tiny cells of the benchmark's families, for CPU tests: same code
paths as the chip cells, at sizes a test run can hold."""

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

DENSE = {
    "arch_id": "internlm2-1.8b", "reference": "dense", "hidden_size": 64,
    "num_hidden_layers": 2, "layer_pattern": ["attn"], "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "tie_word_embeddings": False,
}
# seq 64 < the program's mLSTM chunk of 128: longer sequences overflow
# its chunked scan (see PERF.md, Open questions)
XLSTM = {
    "arch_id": "xlstm-350m", "reference": "xlstm", "hidden_size": 64,
    "num_hidden_layers": 2, "layer_pattern": ["mlstm", "slstm"], "num_heads": 4,
    "mlstm_proj_factor": 2, "mlstm_chunk": 128, "intermediate_size": 0,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
}


def traffic(silos=1, seq_len=16, batch=4):
    return {"silos": silos, "topology": "ring" if silos > 1 else "none",
            "gossip_impl": "ppermute" if silos > 1 else "none", "local_steps": 1,
            "batch_per_silo": batch, "seq_len": seq_len, "dirichlet_alpha": 0.3,
            "lr": 0.05, "momentum": 0.9}


def cell(config, traffic, limits_of):
    """A tiny cell held to the limits of the chip cell ``limits_of``."""
    from chipbench import compare, harness

    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    return harness.Cell("tiny", traffic["silos"], config, traffic,
                        compare.load_limits(REPO, limits_of),
                        bench["end_to_end"], bench["per_layer"])
