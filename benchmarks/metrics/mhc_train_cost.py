"""Bytes and operations the n-stream residual path (manifold-constrained
hyper-connections) needs in one training step, from the configuration
file's Hugging Face keys and the step's tokens. What
``kernel.mhc_train_roofline`` divides by; nothing is taken from the
program and nothing rematerialised is counted.

With ``n = hc_mult`` streams of width ``C = hidden_size``, one sublayer
(attention, or the dense or expert feed-forward) and one token, forward:

- the stream, ``n C`` numbers, is read to form the mixing coefficients
  and the sublayer's input in one pass (a kernel that keeps a tile of
  tokens on the chip does both), and read again to mix the residual;
- the next stream, ``n C`` numbers, is written;
- the sublayer's input, ``C`` numbers, is written and its output, ``C``
  numbers, is read:

``(3 n + 2) C`` numbers in the model's bf16. The backward pass moves
twice that (each array's cotangent beside the array). Sublayers: two a
block, over the stack's blocks and one more block a
multi-token-prediction module.

At the cell's sizes (n 4, C 3,584, 8,192 tokens, 5 + 1 blocks): ``14 x
3,584 x 2 B = 100,352 B`` a token a sublayer forward, ``x 3 x 8,192 =
2.47 GB`` a sublayer a step, ``x 12 = 29.6 GB`` a step: 36.1 ms at 819
GB/s.

Operations: the coefficients' matmul (``n C`` by ``n n + 2 n``, two a
weight), the input mix (``2 n C``) and the update (``2 n (n + 1) C``) a
token forward, three times that with the backward; the Sinkhorn rounds
are ``n n`` numbers a token and are not counted. Against the chip's
matmul peak they are nothing: the path is bound by the stream's bytes.
The maps' own weights (``n C (n n + 2 n)`` a sublayer, 0.34 M) are read
once a step and are not counted either.
"""

BF16 = 2  # bytes
# what the path's ops carry in a trace: the module scopes ``attn_mhc`` and
# ``mlp_mhc`` and the ``mhc/*`` scopes under and beside them
SCOPE = r"mhc/"
PASSES = 3  # forward once, backward twice


def streams(cfg: dict) -> int:
    return cfg.get("hc_mult") or 1


def sublayers(cfg: dict) -> int:
    """Two a block: the stack's and each multi-token-prediction module's."""
    modules = cfg.get("num_nextn_predict_layers") or 0
    return 2 * (cfg["num_hidden_layers"] + modules)


def sublayer_token_forward(cfg: dict) -> dict:
    """One sublayer, one token, forward."""
    n, c = streams(cfg), cfg["hidden_size"]
    return {
        "bytes": float((3 * n + 2) * c * BF16),
        "flops": float(
            2 * n * c * (n * n + 2 * n) + 2 * n * c + 2 * n * (n + 1) * c
        ),
    }


def mhc_train_work(cfg: dict, tokens: int) -> dict:
    """The whole path over one training step of ``tokens`` tokens."""
    one = sublayer_token_forward(cfg)
    scale = PASSES * tokens * sublayers(cfg)
    return {key: scale * value for key, value in one.items()}
