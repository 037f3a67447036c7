"""Entry ``online``: replay epochs of ``OnlineTrainer.fit`` over a packed
``SignatureCache`` (paper §6: hash once, train many epochs).

Set-up writes the raw shards, hashes them once into the cache (its
first pass), builds one trainer and drives it through its first three
SGD steps on three different mini-batches of the cache's own replay,
through ``fit`` -- the window's call -- recording the weights after each
step.  One whole replay epoch then compiles every remaining shape.  The
window runs ``fit(cache, 1)`` on the same trainer, epoch after epoch,
until ``seconds`` have passed; the rate counts every row of every epoch
over the whole time the epochs took.  The check replays the first three
steps in the plain reference and compares each step's loss, the first
gradient (from the weights after one step) and the change of the
weights after three steps.
"""

from __future__ import annotations

import os
import time

import numpy as np

from bench import gen
from bench.entries.prep import _family

FIRST_STEPS = 3


def setup(cfg, traffic, seed, seconds, ctx):
    import jax
    from repro.data.pipeline import SignatureStream
    from repro.train.online import OnlineTrainer, SignatureCache
    fam, _ = _family(cfg, seed)
    b, k = int(cfg["b"]), int(cfg["k"])
    rows = int(traffic["rows_per_shard"])
    raw, _ = gen.webspam_shards(cfg, seed, int(traffic["shards"]), rows,
                                os.path.join(ctx.work, "raw"))
    stream = SignatureStream(raw, fam, b=b, packed=True,
                             chunk_size=int(traffic["chunk_size"]))
    ctx.expect_backend(stream.engine.backend)
    cache = SignatureCache(stream, os.path.join(ctx.work, "cache"))
    for _ in cache:                      # the hashing pass fills the cache
        pass
    trainer = OnlineTrainer(k=k, b=b, kind="svm", average=True,
                            lam=float(traffic["lam"]),
                            eta0=float(traffic["eta0"]),
                            batch_size=int(traffic["batch_size"]))
    bs = trainer.batch_size
    replay = iter(cache)
    sig, labels = next(replay)
    replay.close()
    batches, states = [], []
    for i in range(FIRST_STEPS):
        part = (sig[i * bs:(i + 1) * bs], labels[i * bs:(i + 1) * bs])
        trainer.fit([part], 1)
        m = trainer.state.model
        states.append((np.asarray(jax.device_get(m.w), np.float64),
                       float(m.bias)))
        batches.append((np.asarray(part[0].data), np.asarray(part[1])))
    trainer.fit(cache, 1)                # compiles every window shape
    return {"cfg": cfg, "traffic": traffic, "ctx": ctx, "cache": cache,
            "trainer": trainer, "first": (batches, states), "epochs": []}


def window(st, seconds):
    trainer, cache, ctx = st["trainer"], st["cache"], st["ctx"]
    t0 = time.perf_counter()
    while not st["epochs"] or time.perf_counter() - t0 < seconds:
        with ctx.span("bench.online.epoch"):
            _, stats, _ = trainer.fit(cache, 1)
        st["epochs"] += stats
    st["elapsed"] = time.perf_counter() - t0


def results(st):
    ep, el = st["epochs"], st["elapsed"]
    rows = sum(e.examples for e in ep)
    return {
        "end_to_end": {"train_rows_per_s": rows / el},
        "attempted": rows, "failed": 0,
        "stats": {"window_s": el, "epochs": len(ep),
                  "load_s": sum(e.load_s for e in ep),
                  "train_s": sum(e.train_s for e in ep),
                  "sources": sorted({e.source for e in ep})},
    }


def release(st):
    st.pop("trainer").close()
    st.pop("cache").close()


# -- check ------------------------------------------------------------------

def leaf_gaps(prog, ref):
    """Worst leaf's |norm(prog) - norm(ref)| over max(norm(ref leaf),
    median leaf norm); ``prog``/``ref`` map leaf -> array."""
    norms = {n: float(np.linalg.norm(v)) for n, v in ref.items()}
    med = float(np.median(list(norms.values())))
    return max(abs(float(np.linalg.norm(prog[n])) - norms[n])
               / max(norms[n], med, 1e-30) for n in ref)


def compare(st, ref, prog_states):
    """The three compared numbers for one candidate's weights after each
    of the first steps (the program's, or a control's)."""
    cfg, tr = st["cfg"], st["traffic"]
    batches, _ = st["first"]
    k, b = int(cfg["k"]), int(cfg["b"])
    lam, eta0 = float(tr["lam"]), float(tr["eta0"])
    ref_loss, ref_states = ref.svm_steps(
        [w for w, _ in batches], [y for _, y in batches], k=k, b=b,
        lam=lam, eta0=eta0)
    zero = (np.zeros_like(ref_states[0][0]), 0.0)
    loss_gap = 0.0
    for i, ((words, y), (w, bias)) in enumerate(
            zip(batches, [zero] + prog_states[:-1])):
        got = ref.svm_loss(w, bias, words, y, k=k, b=b, lam=lam)
        loss_gap = max(loss_gap, abs(got - ref_loss[i]) / abs(ref_loss[i]))

    def leaves(state):
        return {"w": state[0], "bias": np.asarray([state[1]])}

    # the first gradient as the optimizer got it: g = (w0 - w1) / eta0
    # from zero weights; leaves whose reference gradient is nought to
    # rounding (under 1e-3 of the median leaf's) move by round-off alone
    g_ref = {n: -v / eta0 for n, v in leaves(ref_states[0]).items()}
    g_prog = {n: -v / eta0 for n, v in leaves(prog_states[0]).items()}
    med = float(np.median([np.linalg.norm(v) for v in g_ref.values()]))
    keep = [n for n, v in g_ref.items() if np.linalg.norm(v) >= 1e-3 * med]
    pick = lambda d: {n: d[n] for n in keep}
    last_ref, last_prog = leaves(ref_states[-1]), leaves(prog_states[-1])
    return {"loss_gap": loss_gap,
            "grad_gap": leaf_gaps(pick(g_prog), pick(g_ref)),
            "update_gap": leaf_gaps(pick(last_prog), pick(last_ref))}


def check(st, ref, n_sample):
    return compare(st, ref, st["first"][1]), {"steps": FIRST_STEPS}


def control(st, ref, n_sample, kind="bfloat16"):
    """A control or a planted fault in the program's place: the reference
    in bfloat16, or the reference over half of each batch."""
    import ml_dtypes
    cfg, tr = st["cfg"], st["traffic"]
    batches, _ = st["first"]
    dtype = ml_dtypes.bfloat16 if kind == "bfloat16" else np.float64
    _, states = ref.svm_steps(
        [w for w, _ in batches], [y for _, y in batches],
        k=int(cfg["k"]), b=int(cfg["b"]), lam=float(tr["lam"]),
        eta0=float(tr["eta0"]), dtype=dtype,
        fault="half" if kind == "half" else "")
    return compare(st, ref, states)
