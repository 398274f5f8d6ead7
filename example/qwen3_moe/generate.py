"""Sample from a checkpoint exported by the pretraining example.

Closes the user loop: ``pretrain.py`` trains and exports sharded
safetensors; this script rebuilds the model in decode mode, loads those
weights through the model_state reader, and runs the jitted KV-cache
generation loop (``d9d_tpu.loop.generate``) — greedy or nucleus sampling,
ragged prompts supported.

Run after the pretraining example (same JSON config so the geometry
matches):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python example/qwen3_moe/generate.py example/qwen3_moe/pretrain.json \
        --max-new-tokens 32 --temperature 0.8 --top-p 0.95

The synthetic corpus is an arithmetic language (token_{i+1} = token_i +
step mod V), so a trained model visibly continues the pattern.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

import flax.linen as nn
import jax
import jax.numpy as jnp

from d9d_tpu.core.compile_cache import enable_compile_cache
from d9d_tpu.loop.generate import generate
from d9d_tpu.model_state import load_params
from d9d_tpu.nn.sdpa import build_sdpa_backend

# reuse the example's config schema + the ONE JSON->model-config mapping
# (guarantees the rebuilt parameter structure matches the export)
from example.qwen3_moe.pretrain import ProjectConfig, build_model_config


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("config", nargs="?",
                    default="example/qwen3_moe/pretrain.json")
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = ProjectConfig.model_validate(
        json.loads(Path(args.config).read_text())
    )
    if cfg.export_to is None:
        raise SystemExit("config has no export_to; run pretrain.py first")

    if args.top_p is not None and args.temperature == 0.0:
        raise SystemExit(
            "--top-p needs --temperature > 0 (greedy ignores sampling)"
        )

    dml = args.prompt_len + args.max_new_tokens
    # decode runs local experts (no EP mesh), forward-only (no remat)
    from d9d_tpu.models.qwen3 import Qwen3MoeCausalLM

    m = cfg.model
    model = Qwen3MoeCausalLM(
        config=build_model_config(m, remat=False),
        sdpa=build_sdpa_backend(),
        dtype=jnp.dtype(m.dtype),
        decode_max_length=dml,
    )

    b, p = args.batch, args.prompt_len
    z = jnp.zeros((b, p), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (b, p))
    template = nn.unbox(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), z, pos, z))
    )
    # the export holds weights only — decode caches/stats init at runtime
    params = load_params(
        cfg.export_to, {"params": template["params"]}
    )["params"]

    # prompts from the synthetic arithmetic language: start s, step k
    import numpy as np

    rng = np.random.default_rng(args.seed)
    starts = rng.integers(0, m.vocab_size, size=(b, 1))
    steps = rng.integers(1, 5, size=(b, 1))
    prompts = (starts + steps * np.arange(p)) % m.vocab_size
    out = generate(
        model,
        params,
        jnp.asarray(prompts, jnp.int32),
        max_new_tokens=args.max_new_tokens,
        temperature=args.temperature,
        top_p=args.top_p,
        rng=jax.random.PRNGKey(args.seed),
    )
    for i in range(b):
        expect = (starts[i, 0] + steps[i, 0] * np.arange(
            p, p + args.max_new_tokens
        )) % m.vocab_size
        got = np.asarray(out[i])
        acc = float((got == expect).mean())
        print(f"prompt[{i}] (step {steps[i, 0]}): {prompts[i].tolist()}")
        print(f"  generated: {got.tolist()}")
        print(f"  pattern accuracy vs arithmetic continuation: {acc:.2f}")


if __name__ == "__main__":
    main()
