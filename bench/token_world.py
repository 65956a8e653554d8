"""What every token family's world shares: the corpus, the document split and
the calibration batch. A token family module's ``make_world`` is
``make_world`` here.

A copy of the program's federated LM task (``repro.launch.train.
build_lm_task``), not an import of it:

- the corpus is a sparse random bigram chain over the configuration's
  vocabulary, each token with ``BRANCHING`` likely successors
  (``repro.data.synthetic.make_lm_corpus``);
- the held-out head of the corpus, a tenth of the mix's ``samples``
  sequences of ``seq_len`` tokens, is the test set;
- the rest is chopped into documents of ``doc_len`` tokens (default four
  sequences), and whole documents are dealt to clients: one each, the rest
  by Dirichlet(``alpha``) proportions, near-uniformly where ``alpha <= 0``
  (``repro.data.partition.document_partition``); each client's documents
  are windowed into sequences that never straddle a document;
- the calibration batch is the protocol's: ``CALIB_BATCH`` sequences of
  uniform random token ids from a fixed ``RandomState(123)``, labels equal
  to the tokens (``repro.data.calibration`` for a token task).

Two things differ, as in the image world (``bench.traffic``): the number of
documents each client holds comes from the mix's fixed ``partition_seed``,
client c holding share c on every seed, and the run's seed draws the corpus
and the order of the documents.
"""
from __future__ import annotations

import bisect

import numpy as np

from bench import traffic as tr

BATCH_KEYS = ("tokens", "labels")
BRANCHING = 8
CALIB_BATCH = 8


def document_counts(n_docs: int, num_clients: int, alpha: float,
                    partition_seed: int) -> np.ndarray:
    """(clients,) documents per client: at least one each, the rest dealt
    by Dirichlet(alpha) proportions, or near-uniformly for ``alpha <= 0``."""
    if n_docs < num_clients:
        raise ValueError(f"{n_docs} documents for {num_clients} clients")
    rng = np.random.RandomState(partition_seed)
    counts = np.ones(num_clients, np.int64)
    rem = n_docs - num_clients
    if alpha > 0:
        counts += rng.multinomial(rem, rng.dirichlet(np.full(num_clients,
                                                             alpha)))
    else:
        counts += np.diff(np.linspace(0, rem, num_clients + 1).astype(int))
    return counts


def bigram_corpus(rng: np.random.Generator, num_tokens: int,
                  vocab: int) -> np.ndarray:
    """A sparse random bigram chain of ``num_tokens`` int32 ids."""
    succ = rng.integers(0, vocab, size=(vocab, BRANCHING))
    cum = np.cumsum(rng.dirichlet(np.full(BRANCHING, 0.5), size=vocab),
                    axis=1).tolist()
    draws = rng.random(num_tokens).tolist()
    out = np.empty(num_tokens, np.int32)
    t = int(rng.integers(vocab))
    for i, u in enumerate(draws):
        out[i] = t
        t = int(succ[t, min(bisect.bisect_right(cum[t], u), BRANCHING - 1)])
    return out


def make_world(config: dict, traffic: dict, seed: int) -> tr.World:
    V = int(config["vocab_size"])
    S = int(traffic["seq_len"])
    doc_len = int(traffic.get("doc_len", 4 * S))
    if doc_len % S:
        raise ValueError(f"doc_len {doc_len} is not a multiple of seq_len {S}")
    C = int(traffic["clients"])
    n_total = int(traffic["samples"])
    n_test = max(2, int(n_total * tr.TEST_FRACTION))
    n_docs = (n_total - n_test) * S // doc_len
    counts = document_counts(n_docs, C, float(traffic["alpha"]),
                             int(traffic["partition_seed"]))
    rng = np.random.default_rng(tr.sub_seeds(seed)["data"])
    corpus = bigram_corpus(rng, (n_test * S) + n_docs * doc_len, V)
    test = corpus[:n_test * S].reshape(n_test, S)
    docs = corpus[n_test * S:].reshape(n_docs, doc_len)
    x_train = docs[rng.permutation(n_docs)].reshape(-1, S)
    sizes = counts * (doc_len // S)
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    crng = np.random.RandomState(tr.CALIB_SEED)
    toks = crng.randint(0, V, size=(CALIB_BATCH, S)).astype(np.int32)
    return tr.World(x_train=x_train, y_train=x_train, offsets=offsets,
                    x_test=test, y_test=test,
                    calib={"tokens": toks, "labels": toks.copy()},
                    num_classes=V)
