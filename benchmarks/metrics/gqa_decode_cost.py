"""Operations and bytes the paged grouped-query decode needs, from the
configuration file's Hugging Face keys and the serving loop's own counts
of the positions its layers attended. What ``kernel.gqa_decode_roofline``
divides by; nothing is taken from the program but those counts.

One decode step of one layer, for a row whose layer reads ``n``
positions (a full layer the row's context, its own new token included; a
window layer that or its window, the smaller): scores of ``h`` query
heads of ``d`` against ``n`` keys, ``2 h n d``, and the weighted sum of
``n`` values of ``d_v``, ``2 h n d_v``. Bytes: the ``n`` key rows and
value rows of the layer's ``h_kv`` key/value heads, ``d + d_v`` bf16
numbers a head a position, read once (the query group shares them).
Queries, outputs and scores stay on the chip. A key row is counted at
its own ``d`` numbers, not at the whole lane tiles the cache stores it
in: storage wider than the algorithm needs is the kernel's cost, not
its work.

A stack with one kind of layer (no ``hybrid_layer_pattern``) is all
full layers at the plain keys.
"""

BF16 = 2  # bytes


def layer_kinds(cfg: dict) -> list[int]:
    """0 full, 1 window, for each layer that is run."""
    n = cfg["num_hidden_layers"]
    return list(cfg.get("hybrid_layer_pattern", [0] * n)[:n])


def kind_sizes(cfg: dict, window: bool) -> dict:
    """``h``, ``h_kv``, ``d`` and ``d_v`` of one kind of layer."""
    prefix = "swa_" if window else ""
    d = cfg.get(prefix + "head_dim", cfg["head_dim"])
    return {
        "h": cfg.get(prefix + "num_attention_heads",
                     cfg["num_attention_heads"]),
        "h_kv": cfg.get(prefix + "num_key_value_heads",
                        cfg["num_key_value_heads"]),
        "d": d,
        "d_v": cfg.get(prefix + "v_head_dim", cfg.get("v_head_dim", d)),
    }


def position_bytes(cfg: dict, window: bool) -> int:
    """Bytes of cache one position of one layer of the kind holds for the
    algorithm, and a step reads."""
    s = kind_sizes(cfg, window)
    return s["h_kv"] * (s["d"] + s["d_v"]) * BF16


def position_flops(cfg: dict, window: bool) -> float:
    s = kind_sizes(cfg, window)
    return 2.0 * s["h"] * (s["d"] + s["d_v"])


def gqa_decode_work(cfg: dict, positions_attended: int,
                    window_positions_attended: int) -> dict:
    """All layers' work over decode steps whose busy slot-steps attended
    ``positions_attended`` positions in each full layer and
    ``window_positions_attended`` in the window layers together (the
    serving loop sums that count over its window layers)."""
    full = layer_kinds(cfg).count(0)
    return {
        "flops": full * positions_attended * position_flops(cfg, False)
        + window_positions_attended * position_flops(cfg, True),
        "bytes": float(
            full * positions_attended * position_bytes(cfg, False)
            + window_positions_attended * position_bytes(cfg, True)
        ),
    }
