"""Data pipeline: client sharding, shared validation set, batching and the
double-buffered host-side round feeder.

The pipeline mirrors the paper's system model: client m holds a local shard
D_m (i.i.d. from p(x, y)); the AP samples the shared/reference set D_o from
the same distribution and broadcasts it before training.  The
:class:`RoundFeeder` overlaps the assembly of round t+1 (the mini-batch
index draw and its put, the dispatch of the device gather from the resident
client shards, RNG/key derivation) with device execution of round t —
cluster selection is the protocol's only true sync point."""
from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, Callable, Iterator, Optional, Tuple

import numpy as np

from ..core.protocol import ClientData
from ..telemetry import NULL_SESSION
from . import synthetic


def dirichlet_relabel(data: ClientData, alpha: float, seed: int = 0) -> ClientData:
    """Beyond-paper non-IID ablation: resample each client's shard with a
    Dirichlet(alpha) class prior (alpha -> inf recovers the paper's i.i.d.
    assumption; alpha ~ 0.1 gives heavily skewed clients).  The shared set
    D_o and the test set stay i.i.d. — the AP draws them from p(x, y)."""
    rng = np.random.default_rng(seed)
    m = data.x.shape[0]
    n_classes = int(data.y.max()) + 1
    pool_x = data.x.reshape(-1, *data.x.shape[2:])
    pool_y = data.y.reshape(-1)
    by_class = [np.where(pool_y == c)[0] for c in range(n_classes)]
    d_m = data.x.shape[1]
    xs, ys = [], []
    for _ in range(m):
        prior = rng.dirichlet([alpha] * n_classes)
        counts = rng.multinomial(d_m, prior)
        idx = np.concatenate([
            rng.choice(by_class[c], size=k, replace=True)
            for c, k in enumerate(counts) if k > 0])
        rng.shuffle(idx)
        xs.append(pool_x[idx])
        ys.append(pool_y[idx])
    return ClientData(x=np.stack(xs), y=np.stack(ys), x0=data.x0, y0=data.y0,
                      x_test=data.x_test, y_test=data.y_test)


def build_image_task(name: str, m_clients: int, d_m: int, d_o: int,
                     n_test: int = 7000, seed: int = 0) -> Tuple[ClientData, "object"]:
    """name: 'mnist' | 'cifar10' — returns (ClientData, CNNConfig)."""
    from ..models.cnn import CIFAR_CNN, MNIST_CNN
    if name == "mnist":
        cfg = MNIST_CNN
        arrs = synthetic.make_classification_data(seed, 10, 28, 1, m_clients, d_m,
                                                  d_o, n_test)
    elif name == "cifar10":
        # lower noise: the deeper CNN gets far fewer updates at reduced
        # scale, so the synthetic task carries more class signal
        cfg = CIFAR_CNN
        arrs = synthetic.make_classification_data(seed, 10, 32, 3, m_clients, d_m,
                                                  d_o, n_test, noise=0.25)
    else:
        raise ValueError(name)
    x, y, x0, y0, xt, yt = arrs
    return ClientData(x=x, y=y, x0=x0, y0=y0, x_test=xt, y_test=yt), cfg


def build_lm_task(vocab: int, seq_len: int, m_clients: int, d_m: int, d_o: int,
                  n_test: int = 64, seed: int = 0) -> ClientData:
    """Token-sequence task for running the protocol over transformer models.
    x arrays hold input tokens; y arrays hold next-token labels."""
    toks = synthetic.make_markov_tokens(seed, vocab, m_clients * d_m + d_o + n_test,
                                        seq_len + 1)
    x_all, y_all = toks[:, :-1], toks[:, 1:]
    n_cl = m_clients * d_m
    x = x_all[:n_cl].reshape(m_clients, d_m, seq_len)
    y = y_all[:n_cl].reshape(m_clients, d_m, seq_len)
    x0 = x_all[n_cl : n_cl + d_o]
    y0 = y_all[n_cl : n_cl + d_o]
    xt = x_all[n_cl + d_o :]
    yt = y_all[n_cl + d_o :]
    return ClientData(x=x, y=y, x0=x0, y0=y0, x_test=xt, y_test=yt)


def minibatches(rng: np.random.Generator, x: np.ndarray, y: np.ndarray,
                batch: int, steps: int) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    for _ in range(steps):
        idx = rng.integers(0, x.shape[0], size=batch)
        yield x[idx], y[idx]


# ---------------------------------------------------------------------------
# double-buffered host pipeline
# ---------------------------------------------------------------------------

def plan_blocks(start: int, stop: int, block: int,
                is_sync: Optional[Callable[[int], bool]] = None):
    """Partition rounds ``[start, stop)`` into ``(t0, k)`` segments of at
    most ``block`` consecutive rounds for round-block execution.

    A segment never extends past a *sync round* — a round whose post-state
    the host must observe before the next round may run (an eval round, a
    checkpoint round): each segment ENDS at the first sync round it reaches,
    because a scanned block only surfaces theta at its final round.
    ``is_sync(t)`` returns whether round ``t`` is such a sync point (``None``
    = no sync constraints); ``block=1`` degenerates to one segment per
    round.  Segments tile ``[start, stop)`` exactly, in order."""
    if block < 1:
        raise ValueError(f"block={block} must be >= 1")
    segments = []
    t = start
    while t < stop:
        k = lane_block_len(t, stop, block, is_sync)
        segments.append((t, k))
        t += k
    return segments


def lane_block_len(t: int, stop: int, block: int,
                   is_sync: Optional[Callable[[int], bool]] = None) -> int:
    """Length of the :func:`plan_blocks` segment starting at round ``t`` —
    the one copy of the sync-round-terminates-segment rule, shared with the
    job-pool scheduler, which re-evaluates it per lane every pool block (a
    pool block runs ``min`` over its active lanes' segment lengths, so a
    lane's sync rounds always land on the last round that lane executes)."""
    k = 1
    while (k < block and t + k < stop
           and not (is_sync is not None and is_sync(t + k - 1))):
        k += 1
    return k

class RoundFeeder:
    """Double-buffered host-side round assembly.

    ``make_round(t)`` — the consumer-supplied closure that samples one
    round's payload (for Pigeon-SL: clusters, stacked mini-batches, derived
    per-client keys, attack state) — is executed on ONE background thread
    strictly in ascending-``t`` order.  That preserves the numpy-RNG and
    JAX-key consumption order the sequential-oracle equivalence contract
    depends on: the streams see exactly the calls the synchronous path would
    make, just earlier in wall-clock time.  Device work issued inside
    ``make_round`` (the mini-batch gather, the key split) is asynchronous:
    it queues behind the round the device is executing.

    At most ``depth`` assembled rounds wait in the queue ahead of the
    consumer (``depth=1`` is classic double buffering).  ``depth=0``
    degrades to fully synchronous assembly — the bound the protocol drivers
    apply at Pigeon-SL+ phase boundaries, where sub-round sampling depends
    on the selected cluster and nothing may run ahead of selection.
    SplitFed's sampling is selection-independent (no sub-rounds, no
    tamper-check key splits), so ``run_splitfed`` reuses the feeder at full
    depth under every threat model.

    ``make_round`` may return arbitrary payloads; ``run_pigeon`` includes a
    per-round randomness-stream snapshot so checkpoints written while the
    feeder runs ahead still capture the synchronous end-of-round state (the
    on-stream resume contract).

    Exceptions raised inside ``make_round`` are re-raised from :meth:`get`
    at the round that failed.  Always :meth:`close` (or use as a context
    manager) so an early exit unblocks the producer thread.
    """

    def __init__(self, make_round: Callable[[int], Any], start: int, stop: int,
                 depth: int = 1, telemetry=None):
        self._make_round = make_round
        self._next = start
        self._tel = NULL_SESSION if telemetry is None else telemetry
        self._thread: Optional[threading.Thread] = None
        if depth <= 0 or stop <= start:
            return
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._produce, args=(start, stop),
            name="pigeon-round-feeder", daemon=True)
        self._thread.start()

    def _produce(self, start: int, stop: int) -> None:
        for t in range(start, stop):
            try:
                with self._tel.span("feeder.assemble", round=t):
                    item = (t, self._make_round(t), None)
            except BaseException as e:  # noqa: BLE001 — relayed to consumer
                item = (t, None, e)
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if self._stop.is_set() or item[2] is not None:
                return

    def get(self, t: int) -> Any:
        """Payload for round ``t``.  Rounds must be consumed in the same
        ascending order they were scheduled."""
        if self._next != t:
            raise RuntimeError(f"RoundFeeder consumed out of order: "
                               f"expected t={self._next}, got t={t}")
        self._next = t + 1
        if self._thread is None:            # depth=0: synchronous fallback
            return self._make_round(t)
        got_t, payload, err = self._q.get()
        if err is not None:
            raise err
        if got_t != t:
            raise RuntimeError(f"RoundFeeder produced t={got_t}, wanted t={t}")
        return payload

    def qsize(self) -> int:
        """Assembled rounds currently buffered ahead of the consumer (the
        telemetry feeder-depth gauge); 0 when running synchronously."""
        q = getattr(self, "_q", None)
        return q.qsize() if q is not None and self._thread is not None else 0

    def close(self) -> None:
        """Stop the producer; safe to call repeatedly / after exhaustion."""
        if self._thread is None:
            return
        self._stop.set()
        try:                                # unblock a producer stuck on put()
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
        self._thread = None

    def __enter__(self) -> "RoundFeeder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
