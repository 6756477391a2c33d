"""Speculative decoding with a genuinely TRAINED draft model.

A draft that IS the target accepts everything (acceptance 1.0, the
mechanical upper bound); this example shows the real pipeline: train a target
LM through the framework, train a much smaller draft on the same data,
then decode speculatively — the draft proposes ``gamma`` tokens per
verify pass, the target accepts a measured fraction, and the output is
STILL token-exact target-greedy (the greedy-acceptance guarantee holds
regardless of draft quality).

Run (CPU mesh rehearsal):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/speculative_draft.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np
import optax


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--new-tokens", type=int, default=24)
    p.add_argument("--gamma", type=int, default=4)
    args = p.parse_args()

    from autodist_tpu import AutoDist
    from autodist_tpu.models import make_generator, transformer_lm
    from autodist_tpu.models.speculative import make_speculative_generator
    from autodist_tpu.models.transformer import dense_attention
    from autodist_tpu.strategy import Parallax

    vocab, seq = 32, 24
    max_len = seq + args.new_tokens + args.gamma + 4
    # Task: x[t+1] = (3*x[t] + 7) mod V with 10% noise.  Learnable by
    # both models, so the trained draft tracks the target closely and
    # acceptance is high — the regime where speculation pays.  (A task
    # only the deeper target can learn drives acceptance toward zero;
    # greedy-acceptance correctness holds either way.)
    rng = np.random.RandomState(0)

    def make_batch(n=64):
        s = np.zeros((n, seq), np.int64)
        s[:, 0] = rng.randint(0, vocab, n)
        for t in range(1, seq):
            s[:, t] = (3 * s[:, t - 1] + 7) % vocab
        noise = rng.random((n, seq)) < 0.10
        s[noise] = rng.randint(0, vocab, int(noise.sum()))
        return {"tokens": s.astype(np.int32)}

    target_spec = transformer_lm(
        vocab_size=vocab, num_layers=3, num_heads=4, head_dim=16,
        d_ff=128, max_len=max_len, seq_len=seq, attn_fn=dense_attention)
    draft_spec = transformer_lm(
        vocab_size=vocab, num_layers=1, num_heads=2, head_dim=8,
        d_ff=32, max_len=max_len, seq_len=seq, attn_fn=dense_attention)

    # Target: trained through the framework session path.
    t_params = target_spec.init(jax.random.PRNGKey(0))
    ad = AutoDist(strategy_builder=Parallax())
    with ad.scope():
        ad.capture(params=t_params, optimizer=optax.adam(3e-3),
                   loss_fn=target_spec.loss_fn,
                   sparse_vars=target_spec.sparse_vars)
    sess = ad.create_distributed_session()
    for i in range(args.steps):
        out = sess.run(make_batch())
        if i % 50 == 0:
            print(f"target step {i:3d} loss {float(out['loss']):.4f}")
    t_params = jax.device_get(sess.params)

    # Draft: a ~30x-smaller model trained on the same stream with a
    # plain optax loop (a draft is typically produced offline).
    d_params = draft_spec.init(jax.random.PRNGKey(1))
    opt = optax.adam(3e-3)
    opt_state = opt.init(d_params)

    @jax.jit
    def draft_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(draft_spec.loss_fn)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    for i in range(args.steps):
        d_params, opt_state, loss = draft_step(d_params, opt_state,
                                               make_batch())
        if i % 50 == 0:
            print(f"draft  step {i:3d} loss {float(loss):.4f}")

    n_t = sum(x.size for x in jax.tree_util.tree_leaves(t_params))
    n_d = sum(x.size for x in jax.tree_util.tree_leaves(d_params))
    print(f"target params: {n_t:,}  draft params: {n_d:,} "
          f"({n_t / n_d:.1f}x smaller draft)")

    prompt = make_batch(4)["tokens"][:, :8]
    sg = make_speculative_generator(target_spec, draft_spec)
    tokens, stats = sg(t_params, d_params, prompt, args.new_tokens,
                       args.gamma)
    acc = float(stats["accepted"]) / max(float(stats["proposed"]), 1.0)
    iters = int(stats["iterations"])
    # The honest comparison is TARGET work: plain batched greedy decode
    # runs the target for new_tokens sequential ticks; speculation runs
    # it for `iters` batched verify passes (plus gamma cheap draft ticks
    # per pass — the draft is the ~30x-smaller model).
    print(f"acceptance rate: {acc:.2f}  "
          f"(target: {args.new_tokens} sequential decode ticks -> "
          f"{iters} batched verify passes, + {args.gamma} draft ticks "
          f"per pass)")

    # The guarantee: speculative output IS target-greedy, token-exact,
    # no matter how good or bad the draft is.
    gen = make_generator(target_spec)
    want = np.asarray(gen(t_params, prompt, args.new_tokens))
    np.testing.assert_array_equal(np.asarray(tokens), want)
    print("speculative output == target greedy decode (token-exact)")

    # A trained draft on a learnable task should be accepted most of
    # the time — this is the number that makes speculation pay.
    assert acc > 0.5, f"trained-draft acceptance unexpectedly low: {acc}"


if __name__ == "__main__":
    main()
