"""A tiny cell for the CPU tests: the served architecture at small widths,
with the Pallas kernels in interpret mode."""
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
for p in (HERE, HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

MODEL = {"num_layers": 4, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256, "window": 4096,
         "rope_theta": 10000.0, "norm_eps": 1e-5, "act": "silu",
         "tie_embeddings": False, "compute_dtype": "bfloat16"}
MEMORY = {"num_slots": 128, "word_size": 16, "num_heads": 4, "k": 4,
          "every_n_layers": 2, "delta": 0.005, "mem_dtype": "float32"}


def config(compute_dtype: str = "bfloat16", lanes: int = 4) -> dict:
    model = dict(MODEL, compute_dtype=compute_dtype)
    overrides = {k: model[k] for k in ("num_layers", "d_model", "num_heads",
                                       "num_kv_heads", "head_dim", "d_ff",
                                       "vocab_size", "compute_dtype")}
    memory = {k: MEMORY[k] for k in ("num_slots", "word_size", "k",
                                     "every_n_layers")}
    return {"name": "tiny", "chips": 1, "lanes": lanes, "prefill_hop": False,
            "program": {"arch": "h2o_danube_3_4b_sam", "overrides": overrides,
                        "memory": dict(memory, backend="pallas-interpret")},
            "model": model, "memory": dict(MEMORY)}


def chat_mix() -> dict:
    return {"loop": "open", "rate_per_s": 4.0, "users": 4, "zipf_s": 1.0,
            "returning": True,
            "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                       "min": 4, "max": 16},
            "output": {"dist": "lognormal", "median": 6, "sigma": 0.8,
                       "min": 2, "max": 10},
            "max_len": 64, "sample_share": 0.5, "check_rows": 4,
            "trace_seconds": 1}
