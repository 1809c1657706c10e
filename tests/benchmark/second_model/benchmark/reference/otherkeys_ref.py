"""The second model's plain reference: the flagship block's, reached through
this model's own key names."""


def dims_of(config: dict) -> dict:
    heads, head_dim = (int(config["num_attention_heads"]),
                       int(config["head_dim"]))
    if heads * head_dim != int(config["hidden_size"]) or \
            int(config["num_key_value_heads"]) != heads:
        raise ValueError("the flagship block has full heads that fill the "
                         "hidden size")
    return {"vocab": int(config["vocab_size"]),
            "d_model": int(config["hidden_size"]), "n_heads": heads,
            "n_kv_heads": int(config["num_key_value_heads"]),
            "head_dim": head_dim, "n_experts": int(config["num_experts"]),
            "d_ff": int(config["moe_intermediate_size"]),
            "top_k": int(config["num_experts_per_tok"]),
            "n_layers": int(config["num_hidden_layers"])}


def flagship_keys(config: dict) -> dict:
    return dict(config, intermediate_size=config["moe_intermediate_size"])

