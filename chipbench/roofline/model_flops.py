"""Model FLOPs of a dense decoder, from its configuration file alone.

The arithmetic of ``launch/model_flops.py`` (2 x the matmul parameters per
token, plus causal attention scores and values, 4 x layers x heads x
head_dim per query-key pair), kept here so a change to the program cannot
change the yardstick.  The output head counts only where a token is
sampled: once per prompt, once per output token."""


def _dims(cfg):
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    hkv = int(cfg["num_key_value_heads"])
    dh = d // h
    f = int(cfg["intermediate_size"])
    n_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f
    return int(cfg["num_hidden_layers"]), n_layer, h * dh, d * int(cfg["vocab_size"])


def prefill_flops(cfg, prompt_len: int) -> float:
    L, n_layer, hd, head = _dims(cfg)
    p = int(prompt_len)
    return (2.0 * L * n_layer * p + 4.0 * L * hd * p * (p + 1) / 2
            + 2.0 * head)


def decode_flops(cfg, context: int) -> float:
    """One output token whose query attends ``context`` keys."""
    L, n_layer, hd, head = _dims(cfg)
    return 2.0 * L * n_layer + 4.0 * L * hd * int(context) + 2.0 * head
