"""A smoke-size Qwen3 configuration and mix for CPU tests of the
harness: the real cells' files with small widths and a short mix."""
import json

from chipbench.lib.spec import BENCH_DIR


def config(heads=4, kv_heads=2):
    from repro.configs.base import LayerSpec, ModelConfig
    c = json.loads((BENCH_DIR / "configs" /
                    "qwen3-0.6b.w3g128.json").read_text())
    c.update(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
             num_attention_heads=heads, num_key_value_heads=kv_heads,
             head_dim=64, vocab_size=512)
    del c["arch"]
    c["_cfg"] = ModelConfig(
        name="tiny-qwen3", family="dense", n_layers=2, d_model=256,
        n_heads=heads, n_kv_heads=kv_heads, head_dim=64, d_ff=512,
        vocab_size=512,
        pattern=(LayerSpec(kind="attn", mlp="dense"),), qk_norm=True,
        rope_theta=1e6, tie_embeddings=True)
    return c


def mix(loop="closed", prefixes=False):
    m = {"loop": loop, "clients": 4, "slots": 4, "max_len": 256,
         "block": 8, "rate": 20.0, "ramp_s": 0.3, "check_requests": 3,
         "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
         "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16}}
    if prefixes:
        m["prefixes"] = {"count": 2, "tokens": 64, "weights": [0.7, 0.3]}
    return m
