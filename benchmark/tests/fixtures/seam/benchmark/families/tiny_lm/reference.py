"""Plain float64 reference of the fixture's model: numpy, gradients by hand;
imports nothing of the system under test."""
import numpy as np


def seeded_weights(seed, vocab, width):
    rng = np.random.default_rng([seed, 0xF1])
    return {"embed": rng.normal(size=(vocab, width)) * 0.5, "out": rng.normal(size=(width, vocab)) * 0.5}


def loss(weights, tokens):
    logits = weights["embed"][tokens[:, :-1]].mean(axis=1) @ weights["out"]
    logits = logits - logits.max(axis=1, keepdims=True)
    logp = logits - np.log(np.exp(logits).sum(axis=1, keepdims=True))
    return float(-logp[np.arange(len(tokens)), tokens[:, -1]].mean()), np.exp(logp)


def train(weights, tokens, batches, lr):
    w = {k: np.array(v, np.float64) for k, v in weights.items()}
    for rows in batches:
        x = tokens[rows]
        hidden = w["embed"][x[:, :-1]].mean(axis=1)
        _, prob = loss(w, x)
        prob[np.arange(len(x)), x[:, -1]] -= 1.0
        dlogits = prob / len(x)
        dembed = np.zeros_like(w["embed"])
        np.add.at(dembed, x[:, :-1], ((dlogits @ w["out"].T) / (x.shape[1] - 1))[:, None, :])
        w = {"embed": w["embed"] - lr * dembed, "out": w["out"] - lr * (hidden.T @ dlogits)}
    return w, loss(w, tokens)[0]
