"""A toy token family, for the harness's tests: proof that a family other
than ResNet runs through ``bench/harness.py`` from new files alone. It is no
cell; ``--root`` points the harness at the fixture's checkout.

The samples: fixed-length RTOK token records (``objects["tokens"]`` ids
each, the last one a target only) made from ``pool_seed``, over the local
store, read by the program's ``TokenDataset``. The program: a dense decoder
(``repro.models.transformer``) at tiny widths through ``make_train_step``.
The reference: the same LM written out below in plain ``jax.numpy``, its
weights drawn from the seed as the program draws its own, and the
benchmark's AdamW step (``bench/reference/model.py``). The interface is in
``bench/families/__init__.py``.
"""
from __future__ import annotations

import math
import struct
from typing import Dict

import numpy as np

from bench import check, spec, storage
from bench.reference import loader as ref_loader
from bench.reference import model as ref_model

PREFIX = "tokens/train/"


def _record(tokens: np.ndarray) -> bytes:
    """RTOK: magic, little-endian u32 count, then int32 ids."""
    return b"RTOK" + struct.pack("<I", len(tokens)) + tokens.astype("<i4").tobytes()


def _decode(record: bytes) -> np.ndarray:
    if record[:4] != b"RTOK":
        raise ValueError("not an RTOK record")
    (n,) = struct.unpack("<I", record[4:8])
    return np.frombuffer(record[8:8 + 4 * n], "<i4")


def load_pool(objects: Dict, pool_dir: str) -> storage.Pool:
    rng = np.random.default_rng(int(objects["pool_seed"]))
    ids = rng.integers(0, int(objects["vocab_size"]),
                       size=(int(objects["pool"]), int(objects["tokens"])))
    return storage.Pool.of([_record(row) for row in ids])


def rehearse(config: Dict, objects: Dict):
    return config, objects


def samples_per_step(config: Dict) -> int:
    return int(config["batch_per_chip"])


def dataset(config: Dict, traffic: Dict, store, seed: int, tracer, fault: str):
    from repro.data.dataset import TokenDataset

    if fault == "alter":
        raise ValueError("the toy family plants no altered samples")
    return TokenDataset(store, int(traffic["keyspace"]), int(config["seq_len"]),
                        prefix=PREFIX, tracer=tracer)


def _program_configs(config: Dict):
    from repro.config import AttentionConfig, ModelConfig, TrainConfig

    heads = int(config["num_heads"])
    mcfg = ModelConfig(
        name=config["name"], family="decoder", num_layers=int(config["num_layers"]),
        d_model=int(config["d_model"]), d_ff=int(config["d_ff"]),
        vocab_size=int(config["vocab_size"]),
        attention=AttentionConfig(kind="mha", num_heads=heads, num_kv_heads=heads,
                                  head_dim=int(config["head_dim"])),
        mlp="swiglu", norm="rmsnorm", dtype="float32", param_dtype="float32", remat=False)
    return mcfg, TrainConfig(**config["train"])


def init_state(config: Dict, seed: int):
    import jax

    from repro.train.steps import init_train_state

    mcfg, tcfg = _program_configs(config)
    key = jax.random.PRNGKey(spec.derive(seed, "weights", 31))
    return jax.jit(lambda k: init_train_state(mcfg, tcfg, k))(key)


def make_step(config: Dict):
    from repro.train.steps import make_train_step

    return make_train_step(*_program_configs(config))


def trainer_options(config: Dict, traffic: Dict) -> Dict:
    return {}


def warm_batch(config: Dict, options: Dict):
    import jax

    b, s = samples_per_step(config), int(config["seq_len"])
    return jax.device_put({"tokens": np.zeros((b, s), np.int32),
                           "targets": np.zeros((b, s), np.int32),
                           "nbytes": np.zeros((b,), np.int64)})


def keep(batch):
    import jax

    tokens, targets = jax.device_get((batch["tokens"], batch["targets"]))
    return np.asarray(tokens), np.asarray(targets)


# -- the plain reference ---------------------------------------------------------


def init_params(key, config: Dict):
    """The program's initialisation, drawn key by key in its order: embedding
    (normal x 0.02), per layer attention (q, k, v, o) then the SwiGLU MLP
    (gate, up, down), each normal over sqrt(fan-in), and the head; norms 1."""
    import jax
    import jax.numpy as jnp

    d, f, v = int(config["d_model"]), int(config["d_ff"]), int(config["vocab_size"])
    h, hd = int(config["num_heads"]), int(config["head_dim"])

    def dense(k, fan_in, shape):
        return jax.random.normal(k, (fan_in, *shape), jnp.float32) * (1.0 / math.sqrt(fan_in))

    def layer(k):
        ks = jax.random.split(jax.random.split(k, 1)[0], 4)
        a, m = jax.random.split(ks[0], 8), jax.random.split(ks[1], 3)
        return {"attn": {"wq": dense(a[0], d, (h, hd)), "wk": dense(a[1], d, (h, hd)),
                         "wv": dense(a[2], d, (h, hd)),
                         "wo": dense(a[3], h * hd, (d,)).reshape(h, hd, d)},
                "ln1": {"scale": jnp.ones((d,))}, "ln2": {"scale": jnp.ones((d,))},
                "mlp": {"w_gate": dense(m[0], d, (f,)), "w_up": dense(m[1], d, (f,)),
                        "w_down": dense(m[2], f, (d,))}}

    ks = jax.random.split(key, 4)
    layers = [layer(k) for k in jax.random.split(ks[1], int(config["num_layers"]))]
    return {"embed": {"w": jax.random.normal(ks[0], (v, d), jnp.float32) * 0.02},
            "blocks": {"sub0": jax.tree.map(lambda *x: jnp.stack(x), *layers)},
            "final_norm": {"scale": jnp.ones((d,))},
            "lm_head": {"w": dense(ks[2], d, (v,))}}


def loss_fn(params, tokens, targets, num_layers: int):
    """Pre-norm decoder: RMSNorm (eps 1e-6), causal attention with rotary
    positions on q and k (halves rotated), SwiGLU, a final norm, the head,
    and the mean cross-entropy of the next token."""
    import jax
    import jax.numpy as jnp

    def rms(x, scale):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale

    def rope(x):
        s, d = x.shape[1], x.shape[-1]
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] / 10000.0 ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    x = params["embed"]["w"][tokens]
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(num_layers):
        p = jax.tree.map(lambda a: a[i], params["blocks"]["sub0"])
        h = rms(x, p["ln1"]["scale"])
        q = rope(jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wq"]))
        k = rope(jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wk"]))
        v = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wv"])
        scores = jnp.einsum("bshk,bthk->bhst", q, k) / math.sqrt(q.shape[-1])
        w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        x = x + jnp.einsum("bshk,hkd->bsd", jnp.einsum("bhst,bthk->bshk", w, v),
                           p["attn"]["wo"])
        h = rms(x, p["ln2"]["scale"])
        x = x + (jax.nn.silu(h @ p["mlp"]["w_gate"]) * (h @ p["mlp"]["w_up"])) @ p["mlp"]["w_down"]
    logits = rms(x, params["final_norm"]["scale"]) @ params["lm_head"]["w"]
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def reference(config: Dict, traffic: Dict, pool, seed: int) -> Dict:
    """The first batches as the sampler's permutation orders the keys, and
    three reference steps on them, in float32 at ``highest`` precision."""
    import jax
    import jax.numpy as jnp

    keyspace, b, s = int(traffic["keyspace"]), samples_per_step(config), int(config["seq_len"])
    store = storage.PoolStore(pool, keyspace, spec.derive(seed, "store"), PREFIX)
    perm = ref_loader.epoch_permutation(keyspace, spec.derive(seed, "sampler"), 0)
    batches = []
    for i in range(check.CHECKED_STEPS):
        rows = [_decode(store.get(f"{PREFIX}{int(j):08d}.rtok")) for j in perm[i * b:(i + 1) * b]]
        batches.append((np.stack([r[:s] for r in rows]), np.stack([r[1:s + 1] for r in rows])))
    layers = int(config["num_layers"])
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: init_params(k, config))(
            jax.random.PRNGKey(spec.derive(seed, "weights", 31)))
        p0 = check.host_leaves(params)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)

        @jax.jit
        def step(params, mu, nu, i, tokens, targets):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets, layers)
            params, mu, nu, g = ref_model.adamw_step(params, mu, nu, grads, i, config["train"])
            return params, mu, nu, loss, g

        losses, g1 = [], None
        for i, (tokens, targets) in enumerate(batches):
            params, mu, nu, loss, g = step(params, mu, nu, jnp.int32(i), tokens, targets)
            losses.append(float(loss))
            if i == 0:
                g1 = check.host_leaves(g)
    return {"batches": batches, "losses": losses, "g1": g1, "p0": p0,
            "p3": check.host_leaves(params)}


def compare(prog: Dict, ref: Dict, config: Dict) -> Dict[str, float]:
    """``batch_mismatch``: rows (tokens or targets) of the first batches
    unlike the reference's; and the training step's numbers."""
    bad = 0
    for (tok, tgt), (rtok, rtgt) in zip(prog["batches"], ref["batches"]):
        bad += int(((tok != rtok).any(1) | (tgt != rtgt).any(1)).sum())
    bad += sum(len(r[0]) for r in ref["batches"][len(prog["batches"]):])
    out = {"batch_mismatch": float(bad)}
    out.update(check.train_numbers(prog, ref, float(config["train"]["beta1"])))
    return out


def flops_per_sample(config: Dict) -> float:
    """6 FLOPs per multiply-add of one sequence's forward pass: the
    projections, the MLP and the head per token, and the attention's
    scores and weighted sum over every position pair."""
    d, f, v = int(config["d_model"]), int(config["d_ff"]), int(config["vocab_size"])
    hd = int(config["num_heads"]) * int(config["head_dim"])
    s, layers = int(config["seq_len"]), int(config["num_layers"])
    per_token = layers * (4 * d * hd + 3 * d * f + 2 * s * hd) + d * v
    return 6.0 * s * per_token


def ingest_bytes_per_sample(config: Dict) -> float:
    return 0.0  # no device epilogue


def readings(config: Dict, seed: int, ref: Dict, prog: Dict) -> Dict:
    return {"program": compare(prog, ref, config)}
