"""Plain float32 DistilBERT forward: the sentiment cells' reference.

Written from the published description (Sanh et al. 2019, "DistilBERT, a
distilled version of BERT"; the Hugging Face ``DistilBertForSequence
Classification`` layout): token + learned position embeddings, layer norm,
then per layer post-LN self-attention and a GELU (erf) feed-forward, the
first token's vector through a ReLU pre-classifier and a linear classifier.
No model code of the repository is imported; the weights are read from the
backend's parameter tree by name.  No kernels, no cache, no batching tricks,
float32 everywhere, matrix multiplications at ``highest`` precision.

Tolerance.  The system runs the same mathematics in bfloat16.  On the chip
at the published widths its positive-class probability differed from this
reference by 0.0025 to 0.0039 at the most over 64 sampled songs (12 seeds)
and by 0.0010 at the median (my chip runs, PR 22), the order of the 0.0057
PR 21 saw between two bfloat16 batch shapes.  The repository's
dynamic int8 forward (``distilbert-int8``, same weights) differed from the
reference by up to 0.0109 on the same songs.  ``TOLERANCE`` is one and a
half times the largest bfloat16 error seen and just over half the int8 error: a forward computed in a
lower precision than the configuration states fails.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 0.006
LN_EPS = 1e-12  # as published for DistilBERT


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _forward(params, token_ids, lengths, n_layers, n_heads):
    enc = params["encoder"]
    batch, seq = token_ids.shape
    x = (enc["word_embeddings"]["embedding"][token_ids]
         + enc["position_embeddings"]["embedding"][jnp.arange(seq)][None])
    x = _layer_norm(x, enc["embed_layer_norm"])
    keep = (jnp.arange(seq)[None, :] < lengths[:, None])[:, None, None, :]
    for i in range(n_layers):
        layer = enc[f"layer_{i}"]
        att = layer["attention"]
        dim = x.shape[-1]
        head = dim // n_heads

        def split(proj):
            # the tree keeps Q/K/V kernels as [dim, heads, head]
            kernel = att[proj]["kernel"].reshape(dim, dim)
            bias = att[proj]["bias"].reshape(dim)
            return (x @ kernel + bias).reshape(batch, seq, n_heads, head)

        q, k, v = split("q_proj"), split("k_proj"), split("v_proj")
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(head)
        scores = jnp.where(keep, scores, -jnp.inf)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        out = (ctx.reshape(batch, seq, dim)
               @ att["o_proj"]["kernel"].reshape(dim, dim)
               + att["o_proj"]["bias"])
        x = _layer_norm(x + out, layer["sa_layer_norm"])
        hidden = jax.nn.gelu(_dense(x, layer["ffn"]["lin1"]), approximate=False)
        x = _layer_norm(x + _dense(hidden, layer["ffn"]["lin2"]),
                        layer["output_layer_norm"])
    pooled = jax.nn.relu(_dense(x[:, 0], params["pre_classifier"]))
    return jax.nn.softmax(_dense(pooled, params["classifier"]), -1)


def positive_probability(params, token_ids, lengths, n_layers, n_heads):
    """P(positive) for each row, float32 at highest matmul precision.

    ``params`` is the backend's tree (any float type, any placement); it is
    brought to the host and cast to float32 here.
    """
    host = jax.tree_util.tree_map(
        lambda a: np.asarray(jax.device_get(a), dtype=np.float32), params
    )
    with jax.default_matmul_precision("highest"):
        probs = jax.jit(_forward, static_argnums=(3, 4))(
            host, jnp.asarray(token_ids, jnp.int32),
            jnp.asarray(lengths, jnp.int32), n_layers, n_heads,
        )
    return np.asarray(probs[:, 1], dtype=np.float64)
