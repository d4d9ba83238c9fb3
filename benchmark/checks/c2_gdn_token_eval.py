"""`c2_token_eval` for a configuration whose model is `--arch=gdn_moe`: the
same `run` (the engine's evaluation at the warm-up boundary against the
configuration's plain reference on the same parameters and sequences:
losses, token accuracy, the backdoor the file states, every (token, expert)
pair accounted for and each held expert's load), under a `contract` that
reads this model's keys: the layer kinds, the linear-attention heads and
widths, the convolution, the full layers' heads and partial rotary
embedding, the softmax router. `run` reaches a model only through its
reference's functions, so it is taken as it is. What the chunked delta rule
and the softmax selection change shows in the losses and the pairs: the
reference runs the recurrence a token at a time and routes over all 512
experts, so a program whose chunks lost a state across their boundary, or
selected by sigmoid, would leave them. On the device beside the engine's
parameters: one sequence's activations, the largest one key-value head's
group of scores, 8 x 4096 x 4096 float32 (0.54 GB), a recurrent state a
block of 64 tokens, and a held expert's hidden layer for all tokens."""

from __future__ import annotations

from benchmark.checks import c2_token_eval as base

PHASE = base.PHASE
run = base.run


def contract(cfg, config) -> None:
    """The flags parse to the model, the cut, the backdoor and the tokens a
    round that the file states (`mfu_pct` multiplies by
    `examples_per_round`)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
        gdn_moe)
    assert cfg.data == "tokens" and cfg.model_arch == "gdn_moe"
    spec = gdn_moe.spec_from_cfg(cfg)
    assert [src for src, _k in spec.layers] == config["layers_held"]
    assert len(spec.layers) == config["num_hidden_layers"]
    assert [k for _i, k in spec.layers] == config["layer_types"]
    for key, have in (
            ("hidden_size", spec.hidden), ("vocab_size", spec.vocab_held),
            ("moe_intermediate_size", spec.moe_ffn),
            ("shared_expert_intermediate_size", spec.shared_ffn),
            ("num_attention_heads", spec.heads),
            ("num_key_value_heads", spec.kv_heads),
            ("head_dim", spec.head_dim), ("rope_theta", spec.rope_theta),
            ("linear_num_key_heads", spec.k_heads),
            ("linear_num_value_heads", spec.v_heads),
            ("linear_key_head_dim", spec.k_dim),
            ("linear_value_head_dim", spec.v_dim),
            ("linear_conv_kernel_dim", spec.conv_taps),
            ("num_experts", spec.experts_held),
            ("expert_offset", spec.expert_offset),
            ("num_experts_per_tok", spec.top_k),
            ("norm_topk_prob", spec.norm_topk),
            ("rms_norm_eps", spec.norm_eps), ("seq_len", cfg.seq_len)):
        assert config[key] == have, key
    assert spec.rotated == int(config["head_dim"]
                               * config["partial_rotary_factor"])
    assert spec.scoring == "softmax"
    assert config["published"]["num_experts"]["source"] == spec.n_experts
    bd = config["backdoor"]
    assert bd["target"] == cfg.target_class
    assert bd["trigger"] == list(range(spec.vocab_held - 3, spec.vocab_held))
    assert cfg.synth_val_size % cfg.eval_bs == 0      # no padded sequence
    assert (cfg.local_ep * cfg.synth_train_size * cfg.seq_len
            == config["examples_per_round"])
    for key in ("val_loss_rtol", "poison_loss_rtol", "acc_tokens",
                "pairs_rtol", "pairs_atol", "pairs_moved_share"):
        assert config["check"][key] > 0, key
