"""RNN-Transducer speech training — the end-to-end story behind
``apex1_tpu.contrib.transducer`` (reference
``apex/contrib/transducer``): an LSTM audio encoder (`apex1_tpu.rnn`,
the hoisted-projection scan RNNs), an LSTM prediction network, the
broadcast-add transducer joint, and the associative-scan α-recursion
RNN-T loss, trained with amp mixed precision + fused Adam on a
synthetic phoneme task (each label held for a few noisy audio frames;
the transducer must recover the label sequence). Greedy RNN-T decoding
(advance t on blank, u on emit) verifies the learned alignment.

``python examples/rnnt_speech.py [--steps 800] [--opt-level O2]``
(defaults reach exact-sequence greedy decode on held-out utterances in
~20s on CPU).
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import flax.linen as nn

from apex1_tpu.amp import Amp
from apex1_tpu.contrib.transducer import (
    transducer_joint, transducer_loss)
from apex1_tpu.core.policy import get_policy
from apex1_tpu.optim.fused_adam import fused_adam
from apex1_tpu.rnn import LSTM

BLANK = 0


class RNNT(nn.Module):
    """Minimal transducer: encoder/predictor LSTMs + joint + vocab head."""

    vocab: int          # incl. blank at index 0
    feat: int
    hidden: int = 64

    @nn.compact
    def __call__(self, audio, dec_in):
        """audio (B, T, feat); dec_in (B, U) label ids with leading
        BLANK (the RNN-T prediction network's <s>). Returns
        (B, T, U, vocab) joint logits."""
        dtype = audio.dtype
        enc, _ = LSTM(self.feat, self.hidden, name="encoder")(
            audio.transpose(1, 0, 2))
        emb = self.param("embed", nn.initializers.normal(0.02),
                         (self.vocab, self.hidden), jnp.float32)
        pred, _ = LSTM(self.hidden, self.hidden, name="predictor")(
            emb[dec_in].astype(dtype).transpose(1, 0, 2))
        h = transducer_joint(enc.transpose(1, 0, 2),
                             pred.transpose(1, 0, 2), relu=True)
        w = self.param("head", nn.initializers.normal(0.02),
                       (self.hidden, self.vocab), jnp.float32)
        return h @ w.astype(h.dtype)


def make_batch(rng, batch, U_lab, frames_per, vocab, feat, proj):
    """Each utterance: U_lab labels from [1, vocab), each held for
    ``frames_per`` audio frames; audio = one-hot @ random projection +
    noise."""
    labels = rng.integers(1, vocab, (batch, U_lab))
    frames = np.repeat(labels, frames_per, axis=1)           # (B, T)
    onehot = np.eye(vocab)[frames]                           # (B, T, V)
    audio = onehot @ proj + rng.normal(0, 0.1, (batch, U_lab * frames_per,
                                                feat))
    dec_in = np.concatenate([np.zeros((batch, 1), np.int64), labels], 1)
    return (jnp.asarray(audio, jnp.float32),
            jnp.asarray(labels, jnp.int32),
            jnp.asarray(dec_in, jnp.int32))


def greedy_decode(model, params, audio, max_symbols=8):
    """Standard RNN-T greedy: at each t emit while argmax != blank
    (bounded), else advance t. Host-loop reference decoder (clarity over
    dispatch count)."""
    B, T, _ = audio.shape
    hyps = []
    for b in range(B):
        y = [BLANK]
        for t in range(T):
            for _ in range(max_symbols):
                logits = model.apply(
                    {"params": params}, audio[b:b + 1],
                    jnp.asarray([y], jnp.int32))
                k = int(jnp.argmax(logits[0, t, len(y) - 1]))
                if k == BLANK:
                    break
                y.append(k)
        hyps.append(y[1:])
    return hyps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=800)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--labels", type=int, default=5)
    ap.add_argument("--frames-per", type=int, default=3)
    ap.add_argument("--vocab", type=int, default=8)
    ap.add_argument("--feat", type=int, default=16)
    ap.add_argument("--opt-level", default="O2")
    args = ap.parse_args()

    model = RNNT(vocab=args.vocab, feat=args.feat)
    rng = np.random.default_rng(0)
    proj = rng.normal(0, 1.0, (args.vocab, args.feat))
    audio, labels, dec_in = make_batch(rng, args.batch, args.labels,
                                       args.frames_per, args.vocab,
                                       args.feat, proj)
    params = model.init(jax.random.key(0), audio, dec_in)["params"]
    n = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"RNN-T: {n/1e3:.0f}k params, opt {args.opt_level}")

    T = args.labels * args.frames_per
    f_len = jnp.full((args.batch,), T, jnp.int32)
    y_len = jnp.full((args.batch,), args.labels, jnp.int32)

    def loss_fn(params, audio, labels, dec_in):
        logits = model.apply({"params": params}, audio, dec_in)
        return transducer_loss(logits, labels, f_len, y_len,
                               blank_idx=BLANK)

    amp = Amp(tx=fused_adam(2e-3), opt_level=args.opt_level)
    state = amp.init(params)
    step = jax.jit(amp.make_train_step(loss_fn))
    t0 = time.time()
    for i in range(args.steps):
        audio, labels, dec_in = make_batch(rng, args.batch, args.labels,
                                           args.frames_per, args.vocab,
                                           args.feat, proj)
        state, m = step(state, audio, labels, dec_in)
        if i % 100 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  nll {float(m['loss']):.4f}")
    print(f"trained {args.steps} steps in {time.time()-t0:.1f}s")

    audio, labels, _ = make_batch(rng, 4, args.labels, args.frames_per,
                                  args.vocab, args.feat, proj)
    # an UNtrained model emits junk at up to max_symbols per frame —
    # every new hypothesis length is a fresh XLA compile in the host
    # decode loop, so short smoke runs cap the emission budget hard
    hyps = greedy_decode(model, state.params, audio,
                         max_symbols=8 if args.steps >= 100 else 2)
    want = [r.tolist() for r in np.asarray(labels)]
    exact = sum(h == w for h, w in zip(hyps, want))
    print(f"greedy exact-sequence match: {exact}/4")
    for h, w in zip(hyps[:2], want[:2]):
        print(f"  ref {w}\n  hyp {h}")


if __name__ == "__main__":
    main()
