"""Benchmark: BERT-base-shaped text fine-tune step throughput.

BASELINE.json's tracked configs include a DeepTextClassifier BERT-base
fine-tune; zero egress, so the graph is the in-repo TextTransformer at
BERT-base dimensions (12 layers, 768 wide, 12 heads, seq 128) with
random weights — identical compute profile to the checkpointed model,
which is what a throughput number measures.

Prints ONE JSON line {"metric", "value", "unit", "batch", "backend"}.
Run: python tools/bench_text.py [batch] [--small]
(--small: 2x128 dims for quick CPU sanity runs)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    batch = int(args[0]) if args else 32
    from bench import device_stamp
    stamp = device_stamp()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from mmlspark_tpu.dl.backbones import TextTransformer

    if "--small" in sys.argv:
        layers, dim, heads = 2, 128, 4
    else:
        layers, dim, heads = 12, 768, 12  # BERT-base shape
    seq, vocab, classes = 128, 30_000, 2

    module = TextTransformer(num_classes=classes, vocab_size=vocab,
                             dim=dim, heads=heads, layers=layers,
                             max_len=seq)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, vocab, size=(batch, seq),
                                   dtype=np.int64).astype(np.int32))
    labels = jnp.asarray(rng.integers(0, classes, size=batch,
                                      dtype=np.int64).astype(np.int32))
    params = module.init(jax.random.key(0), ids)
    opt = optax.adamw(2e-5)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, ids, labels):
        def loss_fn(p):
            logits = module.apply(p, ids)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params, opt_state, _ = step(params, opt_state, ids, labels)  # compile
    jax.block_until_ready(params)
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        params, opt_state, loss = step(params, opt_state, ids, labels)
    jax.block_until_ready(params)
    dt = (time.perf_counter() - t0) / reps
    print(json.dumps({
        "metric": "text_finetune_step",
        "value": round(batch * seq / dt, 1),
        "unit": "tokens/s",
        "batch": batch,
        "shape": f"{layers}L-{dim}d-{heads}h-seq{seq}",
        "backend": jax.default_backend(),
        **stamp,
    }))


if __name__ == "__main__":
    main()
