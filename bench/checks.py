"""The comparison that decides ``correct``.

The program's answers from the window (a sample drawn from the seed)
against the plain reference over the same received slots:

* ``llr_err``: the widest, over the sampled slots, relative gap between
  the program's combined codeword LLRs (what the decoder is fed, after
  LS-CHE, detection, demapping, de-rate-matching and HARQ combining) and
  the reference's: ||program - reference|| / ||reference||;
* ``crc_mismatch``: the share of sampled code blocks whose CRC outcome
  (after LDPC decode) differs from the reference's.

In the closed loop each sampled job is replayed transmission by
transmission, the reference combining with its own earlier rounds, so
HARQ combining through the prior is compared too.
"""
from __future__ import annotations

import numpy as np

import phy
import reference


def _gap(p: np.ndarray, r: np.ndarray) -> float:
    return float(np.linalg.norm(p - r) / max(np.linalg.norm(r), 1e-30))


def replay(chains: list, rungs: dict, dtype: str = "float32") -> list:
    """Reference outputs of every transmission of every chain."""
    out = [[None] * len(c) for c in chains]
    depth = max((len(c) for c in chains), default=0)
    for k in range(depth):
        by_rung = {}
        for ci, chain in enumerate(chains):
            if len(chain) > k:
                by_rung.setdefault(chain[k]["rung"], []).append(ci)
        for name, cis in by_rung.items():
            r = rungs[name]
            txs = [chains[ci][k] for ci in cis]
            prior = np.stack([
                out[ci][k - 1]["cw_llr"] if k else
                np.zeros((r.codewords, r.code.n_mother), np.float32)
                for ci in cis])
            res = reference.receive(
                r, np.stack([t["y_time"] for t in txs]),
                np.asarray([t["noise_var"] for t in txs], np.float32),
                np.asarray([t["rv"] for t in txs]), prior, dtype=dtype)
            for j, ci in enumerate(cis):
                out[ci][k] = {key: v[j] for key, v in res.items()}
    return out


def numbers(llr_pairs: list, block_pairs: list) -> dict:
    """Compared numbers of (program, reference) answers.

    ``llr_pairs`` hold ``cw_llr`` (one slot each); ``block_pairs`` hold
    ``crc_ok`` and ``iters`` (one slot's code blocks each).  Beside the
    two numbers compared, ``iter_mismatch`` (share of code blocks whose
    decoder iteration count differs) is reported for the record.
    """
    gaps = [_gap(p["cw_llr"], r["cw_llr"]) for p, r in llr_pairs]
    crc = np.concatenate([np.ravel(np.asarray(p["crc_ok"]) != r["crc_ok"])
                          for p, r in block_pairs]) if block_pairs else None
    its = np.concatenate([np.ravel(np.asarray(p["iters"]) != r["iters"])
                          for p, r in block_pairs]) if block_pairs else None
    return {"llr_err": max(gaps) if gaps else None,
            "crc_mismatch": float(crc.mean()) if crc is not None else None,
            "iter_mismatch": float(its.mean()) if its is not None else None,
            "n_slots": len(llr_pairs),
            "n_blocks": int(crc.size) if crc is not None else 0}


def pool_reference(y_time: np.ndarray, r: phy.Rung,
                   dtype: str = "float32") -> dict:
    """Reference outputs of slots of rung ``r`` sent at RV 0 with no
    prior; ``y_time`` (N, n_sym, n_sc, n_rx)."""
    n = len(y_time)
    return reference.receive(
        r, y_time, np.full(n, r.noise_var, np.float32), np.zeros(n, int),
        np.zeros((n, r.codewords, r.code.n_mother), np.float32), dtype=dtype)


def backlog(samples: list, blocks: list, ref: dict) -> dict:
    """Sampled LLRs, and CRC outcomes and decoder iterations, against the
    reference's outputs for the same slots.  ``samples``: (reference
    indices, cw_llr); ``blocks``: (reference indices, crc_ok, iters)."""
    pick = lambda i: {k: ref[k][i] for k in ("cw_llr", "crc_ok", "iters")}
    llr = [({"cw_llr": cw[j]}, pick(i))
           for idx, cw in samples for j, i in enumerate(idx)]
    blk = [({"crc_ok": c[j], "iters": it[j]}, pick(i))
           for idx, c, it in blocks for j, i in enumerate(idx)]
    return numbers(llr, blk)
