"""Operations and bytes the absorbed latent decode needs, from the
configuration file's Hugging Face keys and the serving loop's own count
of the positions it attended. What ``kernel.mla_decode_roofline`` divides
by; nothing is taken from the program but that count.

One decode step of one layer, for a row whose context is ``n`` positions
(its own new token included):

- absorb the key up-projection into the query: ``[h, d_nope] x [d_nope,
  r]`` a head, ``2 h d_nope r`` operations;
- scores against the latent rows and the shared rotary key rows,
  ``2 h n (r + d_rope)``, and the weighted sum of the latent rows,
  ``2 h n r``;
- fold the value up-projection out: ``2 h r d_v``.

Bytes: the ``n`` latent rows and rotary-key rows, ``r + d_rope`` bf16
numbers a position, read once a layer a step (both products read the
same rows), and the ``kv_up`` weights ``r x h x (d_nope + d_v)`` once a
layer a step for the whole batch. Queries, outputs and the softmax's
scores stay on the chip.
"""

BF16 = 2  # bytes


def position_bytes(cfg: dict) -> int:
    """Bytes of cache one position of one layer holds and a step reads."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BF16


def mla_decode_work(cfg: dict, positions_attended: int, slot_steps: int,
                    steps: int) -> dict:
    """All layers' work over ``steps`` decode steps in which ``slot_steps``
    busy slot-steps attended ``positions_attended`` positions in all."""
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    d_nope, d_rope, d_v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                           cfg["v_head_dim"])
    layers = cfg["num_hidden_layers"]
    flops = (
        slot_steps * 2.0 * h * r * (d_nope + d_v)
        + positions_attended * 2.0 * h * (2 * r + d_rope)
    )
    weights = r * h * (d_nope + d_v) * BF16
    return {
        "flops": layers * flops,
        "bytes": layers * float(
            positions_attended * position_bytes(cfg) + steps * weights
        ),
    }
