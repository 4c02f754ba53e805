"""moe_train_mfu: the expert-share training step's model FLOPs over the
traced window, over the chips' bf16 peak (%).

Forward FLOPs per step, counted from the configuration and the run's
counts (2 FLOPs per multiply-add; training is three times the forward,
nothing recomputed counts):

- every layer's MLA: its four projections per token (q, the latent and
  rope key, the latent's keys and values, the output), and causal
  attention at the q/k head width for the scores and the v head width for
  the values, over (S + 1) / 2 keys a query on average;
- the leading dense layers' SwiGLU;
- every MoE layer's router (over all experts) and shared experts per
  token, and the held experts' SwiGLU per assignment they computed
  (``moe_rows_held``, summed over layers and the traced steps);
- the head over the vocabulary slice; not the embedding gather.
"""


def mla_params(m) -> int:
    """Matmul weights of one MLA sub-block."""
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dq = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (d * H * dq + d * (r + m["qk_rope_head_dim"])
            + r * H * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + H * m["v_head_dim"] * d)


def fwd_flops(m, tokens: float, seq_len: int, rows_held: float) -> float:
    """Forward FLOPs of ``tokens`` tokens in rows of ``seq_len``, whose
    held experts computed ``rows_held`` assignments."""
    d, L, k = m["d_model"], m["n_layers"], m.get("first_k_dense", 0)
    H = m["n_heads"]
    dq = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    keys = (seq_len + 1) / 2.0
    per_token = (L * (2 * mla_params(m) + 2 * H * (dq + m["v_head_dim"]) * keys)
                 + k * 2 * 3 * d * m["dense_d_ff"]
                 + (L - k) * 2 * d * (m["n_experts"]
                                      + 3 * m["n_shared_experts"] * m["d_ff"])
                 + 2 * d * m["vocab_size"])
    return per_token * tokens + 2 * 3 * d * m["d_ff"] * rows_held


def flops(reading) -> float:
    c = reading.counts
    return 3.0 * fwd_flops(reading.model,
                           c["tokens_per_step"] * c["steps_traced"],
                           c["seq_len"], c["moe_rows_held"])


def read(reading):
    t = reading.trace
    c = reading.counts
    if t is None or not c.get("steps_traced") or "moe_rows_held" not in c:
        return None
    peak = reading.peaks["bf16_flops_per_s"] * reading.chips
    return 100.0 * flops(reading) / t.window_s() / peak
