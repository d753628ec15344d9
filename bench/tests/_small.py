"""Small configurations for the CPU tests: a bench configuration dict and
the program's ModelConfig of the same sizes."""
from repro.config import MLAConfig, ModelConfig


def gqa(n_layers=2, d=64, heads=4, kv=2, d_ff=128, vocab=128):
    m = ModelConfig(name="gqa-test", family="dense", n_layers=n_layers,
                    d_model=d, n_heads=heads, n_kv_heads=kv, d_ff=d_ff,
                    vocab=vocab)
    return _bench(m, "gqa"), m


def mla(n_layers=2, d=64, heads=4, d_ff=128, vocab=128):
    m = ModelConfig(name="mla-test", family="dense", n_layers=n_layers,
                    d_model=d, n_heads=heads, n_kv_heads=heads, d_ff=d_ff,
                    vocab=vocab,
                    mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                                  qk_nope_head_dim=8, qk_rope_head_dim=4,
                                  v_head_dim=8))
    return _bench(m, "mla"), m


def _bench(m, ref):
    c = {"registry": m.name, "reference": ref, "dtype": "bfloat16",
         "hidden_size": m.d_model, "num_hidden_layers": m.n_layers,
         "num_attention_heads": m.n_heads,
         "num_key_value_heads": m.n_kv_heads, "intermediate_size": m.d_ff,
         "vocab_size": m.vocab, "hidden_act": "silu",
         "rms_norm_eps": m.norm_eps, "rope_theta": m.rope_theta,
         "tie_word_embeddings": False,
         "serve": {"slots": 4, "max_len": 1024}}
    if m.mla:
        for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim"):
            c[k] = getattr(m.mla, k)
    return c
