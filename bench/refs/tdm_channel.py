"""Plain reference of the channel plane: placement, Eq. 2 capacities, the
Eq. 4 mixing graph and its density, and the TDM broadcast rounds under
Rayleigh block fading, written out in NumPy from the paper (arXiv:2002.10758
§II) and the MAC the configuration states. It imports nothing of the
program.

Semantics of one TDM round, transmitter by transmitter in id order: the
model is cut into packets of ``packet_bits`` (the tail packet shorter); in
pass 0 the transmitter airs every packet, and in each retransmission pass it
airs again each packet that some intended receiver still lacks. A packet
reaches receiver ``j`` when the instantaneous capacity
``B log2(1 + snr_ij g / B)`` is at least the transmitter's rate, with ``g``
the Exp(1) power gain of the coherence block in which the packet starts and
of the unordered pair ``(i, j)``. The clock advances by each aired packet's
airtime ``size / rate``. ``j`` holds ``i``'s model when it got every packet.
Gains come from a splitmix64 hash of (fading seed, block, pair), so they are
reproducible and reciprocal.

``dtype`` is the precision of every real number; the hash is exact in any.
"""
from __future__ import annotations

import numpy as np

M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def placement(n: int, area_m: float, seed: int, min_sep_m: float = 5.0) -> np.ndarray:
    """Uniform placement in an ``area_m`` square, rejection-sampled so that
    no two nodes are closer than ``min_sep_m``; the stream is keyed by
    ``(seed, 0x10C)``."""
    rng = np.random.default_rng((seed, 0x10C))
    pts: list = []
    while len(pts) < n:
        cand = rng.uniform(0.0, area_m, size=2)
        if all(np.linalg.norm(cand - p) >= min_sep_m for p in pts):
            pts.append(cand)
    return np.stack(pts)


def mean_snr(pos: np.ndarray, p_tx_dbm: float, noise_dbm: float,
             path_loss_exp: float, dtype=np.float64) -> np.ndarray:
    """Eq. 2's linear SNR from log-distance path loss (distance 1 m on the
    diagonal, which no link uses)."""
    pos = np.asarray(pos, dtype)
    diff = pos[:, None, :] - pos[None, :, :]
    d = np.sqrt((diff ** 2).sum(-1))
    d = np.where(d > 0, d, dtype(1.0))
    p = dtype(p_tx_dbm) - dtype(10.0) * dtype(path_loss_exp) * np.log10(d)
    return dtype(10.0) ** ((p - dtype(noise_dbm)) / dtype(10.0))


def planning_capacity(snr: np.ndarray, bandwidth_hz: float,
                      margin_bps: float) -> np.ndarray:
    """Eq. 2 capacity less the fading margin, clipped at 0; a node always
    hears itself."""
    c = bandwidth_hz * np.log2(1.0 + snr / bandwidth_hz)
    c = np.maximum(c - margin_bps, 0.0)
    np.fill_diagonal(c, np.inf)
    return c


def intended(cap: np.ndarray, rates: np.ndarray) -> np.ndarray:
    """Eq. 4 links: ``i -> j`` when ``C_ij >= R_i``; silent transmitters
    (rate 0 or not finite) reach nobody; no self links."""
    a = cap >= rates[:, None]
    a[~(np.isfinite(rates) & (rates > 0))] = False
    np.fill_diagonal(a, False)
    return a


def mixing(links: np.ndarray) -> np.ndarray:
    """Eq. 4 weights: ``W_ij = A_ij / sum_j A_ij`` over the (..., n, n)
    links ``A`` with a unit diagonal added. For the plan, row ``i`` is the
    transmitter (the paper's verbatim Eq. 4); for a realized round, row
    ``j`` is the receiver and ``A_ji`` says that ``j`` holds ``i``'s model."""
    a = np.asarray(links, np.float64).copy()
    idx = np.arange(a.shape[-1])
    a[..., idx, idx] = 1.0
    return a / a.sum(axis=-1, keepdims=True)


def density(w: np.ndarray) -> float:
    """lambda: the largest eigenvalue modulus once the Perron root 1 is set
    aside (paper §III-A)."""
    eig = np.linalg.eigvals(np.asarray(w, np.float64))
    mags = np.abs(eig)
    mags[int(np.argmin(np.abs(eig - 1.0)))] = -np.inf
    return float(mags.max())


def _mix64(z: np.ndarray) -> np.ndarray:
    z = z + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def rayleigh_gains(seed: int, blocks: np.ndarray, i: int, n: int,
                   dtype=np.float64) -> np.ndarray:
    """(len(blocks), n) Exp(1) power gains of transmitter ``i``'s links."""
    j = np.arange(n, dtype=np.uint64)
    ii = np.uint64(i)
    pair = np.minimum(ii, j) * np.uint64(n) + np.maximum(ii, j)
    b = _mix64(np.full(blocks.shape, np.uint64(seed) & M64, np.uint64)
               ^ _mix64(blocks.astype(np.int64).view(np.uint64)))
    h = _mix64(b[:, None] ^ pair[None, :])
    u = (h >> np.uint64(11)).astype(dtype) * dtype(2.0 ** -53)
    with np.errstate(divide="ignore"):     # u rounds up to 1 below float64
        return -np.log1p(-u)


def tdm_rounds(rates, recv, snr, sizes, passes: int, coherence_s: float,
               bandwidth_hz: float, overhead_s: float, compute_s: float,
               fading_seed: int, n_rounds: int, dtype=np.float64):
    """Realize ``n_rounds`` TDM rounds. Returns ``delivered`` (rounds, n, n)
    with ``delivered[r, i, j]`` when ``j`` got all of ``i``'s packets, and
    each round's airtime ``t_comm`` (rounds,)."""
    rates = np.asarray(rates, dtype)
    snr = np.asarray(snr, dtype)
    sizes = np.asarray(sizes, dtype)
    recv = np.asarray(recv, bool)
    n, n_pkts = rates.size, sizes.size
    active = np.isfinite(rates) & (rates > 0)
    safe = np.where(active, rates, dtype(1.0))
    durs = sizes[None, :] / safe[:, None] + dtype(overhead_s)
    bw, coh = dtype(bandwidth_hz), dtype(coherence_s)
    delivered = np.zeros((n_rounds, n, n), bool)
    t_comm = np.zeros(n_rounds, dtype)
    clock = dtype(0.0)
    for r in range(n_rounds):
        t_start = clock
        for i in range(n):
            need = np.broadcast_to(recv[i], (n_pkts, n)).copy()
            for p in range(passes):
                send = (np.ones(n_pkts, bool) if p == 0
                        else need.any(axis=1)) & active[i]
                if not send.any():
                    continue
                d = np.where(send, durs[i], dtype(0.0))
                t_tx = clock + (np.cumsum(d, dtype=dtype) - d)
                blocks = np.floor(t_tx / coh).astype(np.int64)
                uniq, inv = np.unique(blocks, return_inverse=True)
                g = rayleigh_gains(fading_seed, uniq, i, n, dtype)
                cap = bw * np.log2(dtype(1.0) + snr[i][None, :] * g / bw)
                ok = (cap >= rates[i])[inv.reshape(-1)]
                need &= ~(ok & send[:, None])
                clock = clock + d.sum(dtype=dtype)
            delivered[r, i] = recv[i] & ~need.any(axis=0)
        t_comm[r] = clock - t_start
        clock = clock + dtype(compute_s)
    return delivered, t_comm
