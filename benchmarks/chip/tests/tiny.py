"""Small configurations and cells for the CPU tests."""
import cell

FIELDS = list(cell.FIELDS)


def model(hidden=64, heads=4, kv=2, head_dim=16, ff=128, vocab=256, window=16,
          layers=2, tied=False):
    conf = {"arch": "minicpm-2b" if tied else "h2o-danube-1.8b", "model_files": "dense",
            "num_hidden_layers": layers, "hidden_size": hidden,
            "num_attention_heads": heads, "num_key_value_heads": kv, "head_dim": head_dim,
            "intermediate_size": ff, "vocab_size": vocab, "sliding_window": window or None,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": tied,
            "reduced": FIELDS}
    return conf


def serve_cell(conf, limits, batch=2, prompt_lens=(8, 32), gen=8, check_requests=3):
    traffic = {"kind": "serve", "batch": batch, "prompt_lens": list(prompt_lens),
               "gen_tokens": gen, "check_requests": check_requests}
    return _cell(conf, traffic, limits, ["gen_tok_s", "setup_s"])


def _cell(conf, traffic, limits, e2e):
    return {"name": "tiny", "chips": 1, "conf": conf, "model": cell.model_sizes(conf),
            "model_files": cell.model_files(conf["model_files"]),
            "traffic": traffic, "end_to_end": [{"name": n, "unit": "-"} for n in e2e],
            "per_layer": [], "limits": limits, "dir": cell.HERE}
