"""The one traffic generator: per-client batches for a round, from --seed.

The task samplers are copied from the program's `repro.data.tasks` (sst2,
squad, lm) so that a later change to the program cannot change the
yardstick. A traffic file under `bench/traffic/` gives the task and the
sizes; `Pipeline.batch(t)` is a pure function of (seed, t), with every seed
drawing the same shapes.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

# reserved token ids (low range), as in the program's data/tasks.py
PAD, CLS, QUESTION, KEY, POS_VERDICT, NEG_VERDICT = 0, 1, 2, 3, 4, 5
N_RESERVED = 8
# tags that keep the traffic stream apart from every other use of --seed
_TRAFFIC_TAG = 0x7AFF1C


def _lexicons(vocab: int):
    usable = np.arange(N_RESERVED, vocab)
    half = len(usable) // 2
    return usable[:half], usable[half:]


def sample_sst2(vocab: int, seq_len: int, rng: np.random.Generator,
                n: int) -> Dict[str, np.ndarray]:
    """Binary sentiment: the verdict token at the last position."""
    pos_lex, neg_lex = _lexicons(vocab)
    s = seq_len
    tokens = np.zeros((n, s), dtype=np.int32)
    targets = np.zeros((n, s), dtype=np.int32)
    mask = np.zeros((n, s), dtype=np.float32)
    labels = rng.integers(0, 2, size=n)
    for i in range(n):
        dom, sub = (pos_lex, neg_lex) if labels[i] else (neg_lex, pos_lex)
        mix = rng.random(s - 2) < 0.7
        body = np.where(mix, rng.choice(dom, s - 2), rng.choice(sub, s - 2))
        tokens[i, 0] = CLS
        tokens[i, 1:-1] = body
        tokens[i, -1] = QUESTION
        targets[i, -1] = POS_VERDICT if labels[i] else NEG_VERDICT
        mask[i, -1] = 1.0
    return {"tokens": tokens, "targets": targets, "mask": mask,
            "labels": labels.astype(np.int32)}


def sample_squad(vocab: int, seq_len: int, rng: np.random.Generator,
                 n: int) -> Dict[str, np.ndarray]:
    """Extraction: reproduce the token that followed the KEY marker."""
    s = seq_len
    usable = np.arange(N_RESERVED, vocab)
    tokens = rng.choice(usable, size=(n, s)).astype(np.int32)
    targets = np.zeros((n, s), dtype=np.int32)
    mask = np.zeros((n, s), dtype=np.float32)
    answers = rng.choice(usable, size=n)
    key_pos = rng.integers(1, s - 3, size=n)
    for i in range(n):
        tokens[i, key_pos[i]] = KEY
        tokens[i, key_pos[i] + 1] = answers[i]
        tokens[i, -1] = QUESTION
        targets[i, -1] = answers[i]
        mask[i, -1] = 1.0
    return {"tokens": tokens, "targets": targets, "mask": mask,
            "labels": answers.astype(np.int32)}


def sample_lm(vocab: int, seq_len: int, rng: np.random.Generator,
              n: int) -> Dict[str, np.ndarray]:
    """Order-1 Markov stream; every position is scored."""
    v, s = vocab, seq_len
    succ = (np.arange(v) * 31 + 7) % (v - N_RESERVED) + N_RESERVED
    tokens = np.zeros((n, s + 1), dtype=np.int32)
    tokens[:, 0] = rng.integers(N_RESERVED, v, size=n)
    noise = rng.random((n, s)) < 0.15
    rand_tok = rng.integers(N_RESERVED, v, size=(n, s))
    for t in range(s):
        nxt = succ[tokens[:, t]]
        tokens[:, t + 1] = np.where(noise[:, t], rand_tok[:, t], nxt)
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:],
            "mask": np.ones((n, s), dtype=np.float32),
            "labels": np.zeros(n, dtype=np.int32)}


SAMPLERS = {"sst2": sample_sst2, "squad": sample_squad, "lm": sample_lm}


class Pipeline:
    """Round-t global batch [K, b, S] for one traffic file and one seed.

    Duck-types what the program's engine reads from a pipeline
    (`batch(t)`); nothing of the program is imported here.
    """

    def __init__(self, traffic: Dict, vocab: int, seed: int):
        self.task = traffic["task"]
        self.n_clients = int(traffic["clients"])
        self.per_client_batch = int(traffic["batch"])
        self.seq_len = int(traffic["seq_len"])
        self.vocab = int(vocab)
        self.seed = int(seed)
        self._sample = SAMPLERS[self.task]

    def batch(self, t: int) -> Dict[str, np.ndarray]:
        per = []
        for k in range(self.n_clients):
            rng = np.random.default_rng([_TRAFFIC_TAG, self.seed, k, int(t)])
            per.append(self._sample(self.vocab, self.seq_len, rng,
                                    self.per_client_batch))
        return {key: np.stack([p[key] for p in per]) for key in per[0]}
