"""Serve a small LM with batched requests + SLSH-kNN-LM augmentation.

The paper's technique in the serving path: a datastore of (hidden state ->
next token) pairs is indexed with *stratified LSH* (bit-sampling outer layer
on the hidden values, cosine inner layer on heavy buckets), sharded over the
DSLSH grid, and queried at every decode step; the retrieved neighbours'
next-token histogram is interpolated with the LM distribution.

Run:  PYTHONPATH=src python examples/serve_knn_lm.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import dslsh
from repro.data.lm_data import TokenStream
from repro.models import api
from repro.models.api import ModelConfig
from repro.optim import adamw
from repro.runtime import compile_cache
from repro.serve import engine
from repro.train import loop as tl

compile_cache.enable()

cfg = ModelConfig(
    name="serve-demo", family="dense",
    n_layers=4, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
    d_ff=384, vocab=512, mlp="swiglu", q_chunk=64, loss_chunk=64,
)
model = api.build_model(cfg)
stream = TokenStream(cfg.vocab, seed=3)

# -- 1. quick-train so the LM carries signal -------------------------------
opt_cfg = adamw.AdamWConfig(peak_lr=3e-3, warmup_steps=10, total_steps=120)
params = model.init(jax.random.PRNGKey(0))
state = adamw.init(params, opt_cfg)
step_fn = jax.jit(tl.make_train_step(model, opt_cfg))
for b in stream.batches(120, 8, 64):
    params, state, m = step_fn(params, state, {"tokens": jnp.asarray(b["tokens"])})
print(f"trained demo LM to loss={float(m['loss']):.3f}")

# -- 2. build the SLSH datastore over hidden states ------------------------
# keys: final hidden state at position t; value: token t+1
ds_tokens = jnp.asarray(stream.batch(32, 64))


def hidden_states(params, tokens):
    from repro.models import dense as dmod

    x, _ = dmod._embed_inputs(cfg, params, {"tokens": tokens})
    x = dmod._run_layers(cfg, params, x, jnp.arange(tokens.shape[1]), "none")
    return x


h = hidden_states(params, ds_tokens)  # (B, S, D)
keys_data = np.asarray(h[:, :-1].reshape(-1, cfg.d_model), np.float32)
next_tokens = np.asarray(ds_tokens[:, 1:].reshape(-1), np.int32)

deploy = dslsh.grid(nu=2, p=4)
vlo, vhi = float(keys_data.min()), float(keys_data.max())
slsh_cfg = dslsh.make_config(
    dslsh.FamilyConfig(m_out=24, L_out=8, m_in=12, L_in=4, alpha=0.02,
                       val_lo=vlo, val_hi=vhi),
    dslsh.BudgetConfig(k=8, c_max=64, c_in=16, h_max=4, p_max=128),
)
pts, labs, _ = dslsh.pad_to_multiple(keys_data, next_tokens, deploy.cells)
pts_j = jnp.asarray(pts)
index = dslsh.build(jax.random.PRNGKey(9), pts_j, slsh_cfg, deploy)
print(f"SLSH datastore: {keys_data.shape[0]} hidden states, grid nu=2 p=4")

# -- 3. batched serving with the kNN hook ----------------------------------
prompts = [np.asarray(stream.batch(1, 16)[0]) for _ in range(6)]
reqs = [engine.Request(rid=i, tokens=p, max_new=8) for i, p in enumerate(prompts)]


def run_serve(lmbda: float):
    # hidden_fn closure: the hook's carrier is the running token tensor here
    # (ServeEngine instead passes its decode cache as the carrier).
    hook = engine.make_knn_lm_hook(
        index, jnp.asarray(labs),
        hidden_fn=lambda cur: hidden_states(params, cur)[:, -1],
        vocab=cfg.vocab, lmbda=lmbda,
    )
    out_tokens = []
    for r in reqs:
        toks = jnp.asarray(r.tokens, jnp.int32)[None, :]
        logits, cache = model.prefill(params, {"tokens": toks}, 64)
        cur = toks
        gen = []
        for _ in range(r.max_new):
            if lmbda > 0:
                logits = hook(logits, cur)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            gen.append(int(nxt[0, 0]))
            logits, cache = model.decode_step(params, cache, nxt)
            cur = jnp.concatenate([cur, nxt], axis=1)
        out_tokens.append(gen)
    return out_tokens


def accuracy(gens):
    acc = []
    for r, g in zip(reqs, gens):
        # ground truth continuation under the noise-free motif
        ctx = list(r.tokens)
        want = []
        period = stream.period
        # infer phase from the last clean token
        for t in range(len(g)):
            want.append(stream.motif[(np.argmax([np.array_equal(
                stream.motif[(np.arange(len(ctx)) + ph) % period][-4:], ctx[-4:])
                for ph in range(period)]) + len(ctx) + t) % period])
        acc.append(np.mean(np.asarray(g) == np.asarray(want)))
    return float(np.mean(acc))


base = run_serve(lmbda=0.0)
knn = run_serve(lmbda=0.3)
print(f"LM-only   continuation accuracy: {accuracy(base):.2f}")
print(f"+SLSH-kNN continuation accuracy: {accuracy(knn):.2f}")
print("served", len(reqs), "batched requests (latency-first engine)")
