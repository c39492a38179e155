"""What the decoder-only language models of `models/` are built from, one
module a decision: `rope` (how positions turn q and k), `attention` (how
attention is tiled and masked, what a recomputed layer keeps), `delta` (how
a mixer carries a state along the sequence: the gated delta rule in chunks,
by the kernels of `ops/delta_rule.py` on a TPU at heads of 128 lanes and
whole chunks, by `chunked_rule` in XLA's own operations elsewhere), `ssm` (a state-space
mixer with a scalar decay a head, Mamba-2: the scan in chunks, a group of
heads at a time, in XLA's own operations; a grouped gated norm), `experts` (how tokens reach their experts and come back), `common` (what all
of them share). A model file imports blocks and never another model; a block
imports no model (`tests/test_layering.py`).

Beyond what Mellum 2's published config uses, the blocks offer the models
that share them: a shared expert, sigmoid scores with a selection bias, a
scale and a constant in the weights' sum (`Experts`, `GatedMLP`),
adjacent-pair rotary (`apply_rope`), values of a head size of their own and
a key/value head for every query head (`plain_attention`,
`splash_attention`), a norm over each q and k head before the turn
(`Attention(qk_norm=True)`, under the scope `qk_norm` inside `attn_proj`),
a layer without positions (`Attention(positions=False)`: q and k are not
turned, only scaled, and the layer opens no `rope` scope), a sigmoid gate
on the attention's output (`Attention(gate=True)`, `gated_output`, under
the scope `attn_gate` inside `attn_proj`), a turn of a head's FIRST entries
(`Attention(rope_lead=True)`, `apply_rope(lead=True)`: a
`partial_rotary_factor`), zero-centred norms (`RMSNorm(zero_centred=True)`,
`Attention(qk_norm_zero_centred=True)`: times `1 + scale`, the scale from
zero), a sigmoid gate on the shared expert (`Experts(shared_gate=True)`),
squared-ReLU experts of two matrices (`Experts(form=RELU2)`,
`GatedMLP(form=RELU2)`: `W2 relu(W1 x)^2`, no leaf `w3`), a bias on the
mixers' convolution (`delta.conv_silu(x, taps, bias)`).
Which model sets which field:

    field                        mellum2  joyai_flash  lfm2_moe  afmoe        qwen3_next         nemotron_h
    Attention  qk_norm           -        (own MLA)    yes       yes          yes, zero-centred  -
               positions=False   -        (own MLA)    -         full layers  -                  yes
               rope_lead         -        (own MLA)    -         -            yes (64 of 256)    -
               gate              -        (own MLA)    -         yes          yes                -
    RMSNorm    zero_centred      -        -            -         -            yes                -
    Experts    scoring           softmax  sigmoid      sigmoid   sigmoid      softmax            sigmoid
               select_bias       -        yes          yes       yes          -                  yes
               scale             1        2.5          1         2.826        1                  2.5
               sum_eps           0        0            1e-6      1e-20        0                  0
               shared_width      0        768          0         1024         512                3712
               shared_gate       -        -            -         -            yes                -
               form              gated    gated        gated     gated        gated              relu2
    GatedMLP   leading dense     -        1            2         2            -                  -
    delta      GatedDeltaNet     -        -            -         -            3 layers in 4      -
               conv_silu bias    -        -            -         -            -                  yes
    ssm        Mamba2Mixer       -        -            -         -            -                  `M` blocks

Device scopes (`jax.named_scope`; `benchmarks/model_scopes.py` reads the
first five, `benchmarks/scope_tree.py` the whole path): `attn_window`,
`attn_full`, `moe_router`, `moe_experts`, `lm_head`; inside `moe_experts`
`moe_to_rows`, `moe_to_tokens`, `moe_gate`, `moe_product_glue` and, around
the products' kernels `grouped_fwd`, `grouped_dx`, `grouped_dw` and their
schedule, `moe_product` (a name no reader lists: its time is `moe_experts`'
own); inside `gdn_rule` the rule's kernels `gdn_fwd`, `gdn_fwd_kept`,
`gdn_bwd` (they carry the path, so the scope's reader counts them); inside `moe_router` `moe_route_sort`; `attn_proj` (the four
projections, not around attention proper) with `rope` inside it (the rotary
turn whole, `rope.py`); `rms_norm` (every instance); the models' own `embed`.
A new scope goes INSIDE the one a metric reads (docs/OBSERVABILITY.md,
"Device scopes").
Counters (returned with `return_counters=True`, logged through the loss
function's auxiliary output): `moe_held_assignments`, `moe_room_used`,
`moe_load_max_over_mean`, `moe_tokens_unserved`; a model with gated
attention adds `attn_gate_mean` (`models/afmoe.py`), a gated shared expert
`moe_shared_gate_mean`, the linear mixer `gdn_decay_mean`, `gdn_beta_mean`
and `gdn_state_rms` (`delta.py`, which also has its scopes: `linear_attn`
and the five inside it), the state-space mixer `ssm_dt_mean`,
`ssm_decay_mean` and `ssm_state_rms` (`ssm.py`, with its scopes: `ssm` and
the five inside it).
"""
