"""Plain numpy reference of SEGA-DCIM's macro cost model (paper Tables
II-VI), written from the paper's formulas and independent of the program
(``src/repro/core``): the same genome gives the same four objectives
[area, delay, energy, -throughput] and the same storage violation.

A genome is (j, h, kk): N = B_w * 2^j columns, H = 2^h rows, k = 2^kk
input bits per cycle, and L = 2^l weights per compute unit with
l = log2(W_store) - j - h, clamped into [0, 6] for the costs; the
violation is how far l lies outside [0, 6].  Costs are normalised to a
NOR2 gate of TSMC28 (Table III); the weight-selection mux of Fig. 5 is
left out, as in the printed Table V.

``dtype`` is the arithmetic: float64 for the reference, bfloat16 (each
operation rounded, through ``ml_dtypes``) for the control.
"""
from __future__ import annotations

import math

import ml_dtypes
import numpy as np

# (is_fp, B_w, B_x or B_M, B_E) per format, from the paper's sec. I and IV.
FORMATS = {
    "int2": (False, 2, 2, 0), "int4": (False, 4, 4, 0),
    "int8": (False, 8, 8, 0), "int16": (False, 16, 16, 0),
    "fp8": (True, 4, 4, 4), "fp16": (True, 11, 11, 5),
    "bf16": (True, 8, 8, 8), "fp32": (True, 24, 24, 8),
}
# Table III, normalised to NOR2.
A = dict(NOR=1.0, OR=1.3, MUX=2.2, HA=4.3, FA=5.7, DFF=6.6, SRAM=2.2)
D = dict(NOR=1.0, OR=1.0, MUX=2.2, HA=2.5, FA=3.3)
E = dict(NOR=1.0, OR=2.3, MUX=3.0, HA=6.9, FA=8.4, DFF=9.6)
L_MAX_LOG2 = 6
DTYPES = {"float64": np.float64, "bfloat16": ml_dtypes.bfloat16}


class _Model:
    def __init__(self, dtype):
        self.t = DTYPES[dtype]

    def c(self, x):
        return np.asarray(x).astype(self.t)

    def log2(self, n):
        return np.log2(np.maximum(self.c(n), self.c(1.0)))

    # Table II: n-bit adder, L:1 selector, n-bit barrel shifter, comparator.
    def add(self, n, lib):
        return (self.c(n) - 1) * self.c(lib["FA"]) + self.c(lib["HA"])

    def sel(self, n, lib):
        return (self.c(n) - 1) * self.c(lib["MUX"])

    def sel_delay(self, n):
        return self.log2(n) * self.c(D["MUX"])

    def shift(self, n, lib):
        return self.c(n) * self.sel(n, lib)

    def shift_delay(self, n):
        return self.log2(n) * self.sel_delay(n)

    # Table IV components.
    def tree(self, H, k, lib, h):
        out = self.c(np.zeros(np.broadcast(H, k).shape))
        for n in range(12):
            on = n < h
            term = self.add(k + n, lib) * H / self.c(2.0 ** (n + 1))
            out = out + np.where(on, term, self.c(0.0)).astype(self.t)
        return out

    def tree_delay(self, k, h):
        out = self.c(np.zeros(np.shape(k)))
        for n in range(12):
            out = out + np.where(n < h, self.add(k + n, D), self.c(0.0)).astype(self.t)
        return out

    def accu(self, B, lib):
        return B * self.c(lib["DFF"]) + self.shift(B, lib) + self.add(B, lib)

    def fusion(self, B_w, w, lib):
        return (B_w - 1) * (w - 1) * self.c(lib["FA"]) + (B_w + w - 1) * self.c(lib["HA"])

    def convert_tree(self, B_r, a_or, a_mux):
        levels = np.ceil(self.log2(B_r))
        out = self.c(np.zeros(np.shape(B_r)))
        for lv in range(1, 8):
            frac = B_r / self.c(2.0 ** lv)
            term = np.maximum(frac - 1, self.c(0.0)) * self.c(a_or) + frac * self.c(a_mux)
            out = out + np.where(lv <= levels, term, self.c(0.0)).astype(self.t)
        return out

    def int_core(self, N, H, L, k, B_w, B_x, h):
        lgH = self.c(h)
        B = B_x + lgH                             # accumulator width
        area = (N * H * L * self.c(A["SRAM"]) + N * H * k * self.c(A["NOR"])
                + N * self.tree(H, k, A, h) + N * self.accu(B, A)
                + N / B_w * self.fusion(B_w, B, A))
        energy = (N * H * k * self.c(E["NOR"]) + N * self.tree(H, k, E, h)
                  + N * self.accu(B, E) + N / B_w * self.fusion(B_w, B, E))
        d_path = (self.c(D["NOR"]) + self.tree_delay(k, h)
                  + self.shift_delay(B) + self.add(B, D))
        d_fusion = (B - 1) * self.c(D["HA"]) + (B_w - 1) * self.c(D["FA"])
        return area, np.maximum(d_path, d_fusion), energy

    def evaluate(self, fmt: str, w_store: int, genes):
        is_fp, b_w, b_x, b_e = FORMATS[fmt]
        g = np.asarray(genes, np.int64).reshape(-1, 3)
        j, h, kk = g[:, 0], g[:, 1], g[:, 2]
        l_raw = int(math.log2(w_store)) - j - h
        lc = np.clip(l_raw, 0, L_MAX_LOG2)
        N = self.c(b_w * 2.0 ** j)
        H, L, k = self.c(2.0 ** h), self.c(2.0 ** lc), self.c(2.0 ** kk)
        B_w, B_x, B_E = self.c(b_w), self.c(b_x), self.c(b_e)
        area, delay, energy = self.int_core(N, H, L, k, B_w, B_x, h)
        if is_fp:
            lgH = self.c(h)
            B_r = B_w + B_x + lgH
            area = area + ((H - 1) * self.add(B_E, A) + H * self.shift(B_x, A)
                           + N / B_w * (self.convert_tree(B_r, A["OR"], A["MUX"])
                                        + self.add(B_E, A)))
            energy = energy + ((H - 1) * self.add(B_E, E) + H * self.shift(B_x, E)
                               + N / B_w * (self.convert_tree(B_r, E["OR"], E["MUX"])
                                            + self.add(B_E, E)))
            d_align = np.maximum(lgH * self.add(B_E, D), self.shift_delay(B_x))
            d_conv = self.log2(B_r) * self.c(D["OR"] + D["MUX"]) + self.add(B_E, D)
            delay = np.maximum(np.maximum(d_align, delay), d_conv)
        thpt = N / B_w * H * self.c(2.0) * (k / B_x) / delay
        F = np.stack([area, delay, energy, -thpt], -1).astype(np.float64)
        v = (np.maximum(-l_raw, 0) + np.maximum(l_raw - L_MAX_LOG2, 0)).astype(np.float64)
        return F, v


def evaluate(fmt: str, w_store: int, genes, dtype: str = "float64"):
    """(objectives (n, 4), violation (n,)) of ``genes`` (n, 3)."""
    return _Model(dtype).evaluate(fmt, w_store, genes)


def dominated(F) -> np.ndarray:
    """Which rows of F (n, 4), all minimised, another row dominates."""
    F = np.asarray(F, np.float64)
    le = (F[None, :, :] <= F[:, None, :]).all(-1)   # [i, j]: j <= i everywhere
    lt = (F[None, :, :] < F[:, None, :]).any(-1)
    return (le & lt).any(1)


# The paper's bounds (sec. IV): N > 4 B_w (j >= 3), 2 <= H <= 2048
# (1 <= h <= 11), L <= 64 (0 <= l <= 6), k <= B_x; and N H L = W_store B_w,
# so l = log2(W_store) - j - h.
J_MIN, H_MIN_LOG2, H_MAX_LOG2 = 3, 1, 11


def feasible_genomes(fmt: str, w_store: int) -> np.ndarray:
    """Every feasible genome (j, h, kk) of a scenario, shape (n, 3)."""
    s = int(math.log2(w_store))
    kk_max = int(math.floor(math.log2(FORMATS[fmt][2])))
    return np.array([(j, h, kk)
                     for j in range(J_MIN, s - H_MIN_LOG2 + 1)
                     for h in range(H_MIN_LOG2, H_MAX_LOG2 + 1)
                     if 0 <= s - j - h <= L_MAX_LOG2
                     for kk in range(kk_max + 1)], np.int64)


def front_gaps(fmt: str, w_store: int, genes, tol: float) -> tuple[int, int]:
    """(missing, extra) of a returned front ``genes`` against the exact
    Pareto set of every feasible genome, by brute force in float64.

    Two objective values within ``tol`` of each other (relative) and not
    equal may be ordered either way by a program whose objectives are
    that close to the reference's, so a genome whose status such a pair
    decides is left out of both counts.  Missing: a genome that no other
    comes within ``tol`` of dominating, absent from the front.  Extra: a
    returned genome that is infeasible, or that another dominates with
    every unequal objective better by more than ``tol``."""
    G = feasible_genomes(fmt, w_store)
    F, _ = evaluate(fmt, w_store, G)
    d = F[None, :, :] - F[:, None, :]             # [p, q]: F_q - F_p
    slack = tol * np.abs(F)[:, None, :]
    eq = d == 0
    sure_lt = d < -slack
    maybe_le = d <= slack
    dom_sure = (sure_lt | eq).all(-1) & sure_lt.any(-1)       # q surely dominates p
    dom_maybe = maybe_le.all(-1) & ~eq.all(-1)                # q may dominate p
    np.fill_diagonal(dom_maybe, False)
    on_front = ~dom_maybe.any(1)
    off_front = dom_sure.any(1)
    index = {tuple(g): i for i, g in enumerate(G.tolist())}
    got = {tuple(g) for g in np.asarray(genes, np.int64).reshape(-1, 3).tolist()}
    missing = sum(1 for i in np.flatnonzero(on_front) if tuple(G[i]) not in got)
    extra = sum(1 for g in got if g not in index or off_front[index[g]])
    return missing, extra


def ranks(F, v) -> np.ndarray:
    """Front ranks (0 = best) of Deb's constrained non-dominated sort:
    a feasible point dominates an infeasible one, the smaller violation
    dominates among infeasible points, Pareto dominance among feasible."""
    F = np.asarray(F, np.float64)
    v = np.asarray(v, np.float64)
    feas = v <= 0
    pd = ((F[:, None, :] <= F[None, :, :]).all(-1)
          & (F[:, None, :] < F[None, :, :]).any(-1))        # [i, j]: i dominates j
    D = (feas[:, None] & feas[None, :] & pd) | (v[:, None] < v[None, :])
    out = np.full(len(F), -1)
    left = np.ones(len(F), bool)
    r = 0
    while left.any():
        front = left & ~(D & left[:, None]).any(0)
        out[front] = r
        left &= ~front
        r += 1
    return out
