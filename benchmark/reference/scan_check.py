"""Plain reference for the day scan: raw columns in, every event's score
out, in straightforward `jax.numpy` at the precision the configuration
states (float32; the score table at the backend's default matmul
precision). It imports nothing of the program and takes none of its
tables: it is given the trained model as the fit left it (theta, phi, the
sorted vocabulary of packed word keys, the sorted document addresses, the
fitted bin edges) and builds its own extension for the unseen, its own
score table, its own word keys and its own look-ups.

A word, as the configuration files state it:
  flow  pbin | bbin << 6 | hbin << 12 | pclass << 18 | proto << 35, with
        pclass the privileged port of the pair (the smaller if both are,
        65536 if neither), proto the index in the fitted sorted protocol
        table (255 if absent), and the bins searchsorted(side=right) of
        hour, log1p(bytes), log1p(packets) against the fitted edges;
  dns   tld | rcode << 1 | qtype << 9 | nlabels << 25 | ebin << 28 |
        slbin << 34 | hbin << 40 | flbin << 46, the name's features taken
        once per unique name on the host.
An event's score is the least of table[doc, word] over its documents (two
for flow, one for dns), kept if it is under tol. The answer due is the
`max_results` events of least score, ties to the lower index.
"""

from __future__ import annotations

import math

import numpy as np

REF_BLOCK = 1 << 22


def _unpack(keys: np.ndarray, layout) -> dict:
    out, shift = {}, 0
    for name, bits in layout:
        out[name] = (keys >> shift) & ((1 << bits) - 1)
        shift += bits
    return out


def extend_for_unseen(theta: np.ndarray, phi: np.ndarray):
    """One more document at the uniform mixture, one more word at half
    the rarest word's probability in every topic."""
    k = theta.shape[1]
    theta_x = np.concatenate([theta, np.full((1, k), 1.0 / k, np.float32)])
    phi_x = np.concatenate([phi, phi.min(axis=0, keepdims=True) * 0.5])
    return theta_x.astype(np.float32), phi_x.astype(np.float32)


def _lookup(table, ids, keys, fill):
    import jax.numpy as jnp
    pos = jnp.clip(jnp.searchsorted(table, keys), 0, table.shape[0] - 1)
    return jnp.where(table[pos] == keys, ids[pos], jnp.int32(fill))


def _rank_vocab(keys: np.ndarray, layout):
    """The packed 64-bit vocabulary as int32 tables (JAX runs without
    64-bit integers): per field its distinct values, sorted; per word the
    mixed-radix number of its fields' ranks, sorted, with the order."""
    f = _unpack(np.asarray(keys, np.int64), layout)
    uniq = {n: np.unique(f[n]) for n, _ in layout}
    key = np.zeros(len(keys), np.int64)
    for n, _ in layout:
        key = key * len(uniq[n]) + np.searchsorted(uniq[n], f[n])
    assert np.prod([len(u) for u in uniq.values()]) < 2 ** 31
    order = np.argsort(key, kind="stable")
    return ({n: u.astype(np.int32) for n, u in uniq.items()},
            key[order].astype(np.int32), order)


def _entropy(s: str) -> float:
    if not s:
        return 0.0
    n = len(s)
    return -sum(c / n * math.log2(c / n)
                for c in (s.count(ch) for ch in set(s)))


def make_scorer(config: dict, model: dict, cols: dict):
    """Returns (casts, consts, block_fn): `casts` names each staged column
    with its device dtype; `block_fn(consts, *columns)` gives the block's
    scores (inf where the event is not under tol). The tables ride in
    `consts` as arguments, so the compiled block holds none of them."""
    import jax
    import jax.numpy as jnp

    theta_x, phi_x = extend_for_unseen(np.asarray(model["theta"]),
                                       np.asarray(model["phi_wk"]))
    d_x, v_x = theta_x.shape[0], phi_x.shape[0]
    unseen_d, unseen_w = d_x - 1, v_x - 1
    layout = config["word_key_layout"]
    tol = float(config["tol"])
    uniq, key_sorted, order = _rank_vocab(model["word_key_sorted"], layout)
    consts = {
        "table": jax.jit(lambda t, p: jnp.matmul(t, p.T))(
            jnp.asarray(theta_x), jnp.asarray(phi_x)).ravel(),
        "doc_sorted": jnp.asarray(np.asarray(model["doc_u32_sorted"],
                                             np.uint32)),
        "doc_ids": jnp.asarray(np.asarray(model["doc_u32_ids"], np.int32)),
        "edges": {k: jnp.asarray(np.asarray(v, np.float32).ravel())
                  for k, v in model["edges"].items() if k != "proto_classes"},
        "uniq": {n: jnp.asarray(u) for n, u in uniq.items()},
        "key_sorted": jnp.asarray(key_sorted),
        "ids_sorted": jnp.asarray(
            np.asarray(model["word_key_ids"], np.int32)[order]),
    }

    def bins(c, name, x):
        return jnp.searchsorted(c["edges"][name], x,
                                side="right").astype(jnp.int32)

    def doc_id(c, ip):
        return _lookup(c["doc_sorted"], c["doc_ids"], ip, unseen_d)

    def score(c, wid, *dids):
        s = c["table"][dids[0] * jnp.int32(v_x) + wid]
        for d in dids[1:]:
            s = jnp.minimum(s, c["table"][d * jnp.int32(v_x) + wid])
        return jnp.where(s < tol, s, jnp.inf)

    def word_id(c, fields: dict):
        """Each field to its rank among the vocabulary's values of that
        field; a value the vocabulary never saw makes the word unseen."""
        key, hit = jnp.int32(0), True
        for n, _ in layout:
            u, v = c["uniq"][n], fields[n].astype(jnp.int32)
            pos = jnp.clip(jnp.searchsorted(u, v), 0, u.shape[0] - 1)
            hit = hit & (u[pos] == v)
            key = key * jnp.int32(u.shape[0]) + pos
        wid = _lookup(c["key_sorted"], c["ids_sorted"], key, unseen_w)
        return jnp.where(hit, wid, jnp.int32(unseen_w))

    if config["datatype"] == "flow":
        fitted = [str(p) for p in model["edges"]["proto_classes"]]
        consts["remap"] = jnp.asarray(np.asarray(
            [fitted.index(p) if p in fitted else 255
             for p in map(str, cols["proto_classes"])], np.int32))
        casts = [("sip_u32", np.uint32), ("dip_u32", np.uint32),
                 ("sport", np.int32), ("dport", np.int32),
                 ("proto_id", np.int32), ("hour", np.float32),
                 ("ibyt", np.float32), ("ipkt", np.float32)]

        @jax.jit
        def block(c, sip, dip, sport, dport, proto, hour, byt, pkt):
            s_low, d_low = sport <= 1024, dport <= 1024
            pclass = jnp.where(s_low & d_low, jnp.minimum(sport, dport),
                               jnp.where(s_low, sport,
                                         jnp.where(d_low, dport, 65536)))
            wid = word_id(c, {
                "pbin": bins(c, "log_ipkt", jnp.log1p(pkt)),
                "bbin": bins(c, "log_ibyt", jnp.log1p(byt)),
                "hbin": bins(c, "hour", hour),
                "pclass": pclass, "proto": c["remap"][proto]})
            return score(c, wid, doc_id(c, sip), doc_id(c, dip))

        return casts, consts, block

    if config["datatype"] == "dns":
        tlds = set(config["valid_tlds"])
        sub_len, ent, nlab, tld = [], [], [], []
        for q in cols["qnames"]:
            name = str(q).rstrip(".").lower()
            labels = name.split(".") if name else []
            sub = ".".join(labels[:-2]) if len(labels) > 1 else ""
            sub_len.append(float(len(sub)))
            ent.append(_entropy(sub))
            nlab.append(min(len(labels), 6))
            tld.append(int(bool(labels) and labels[-1] in tlds))
        e64 = model["edges"]
        per_name = {
            "slbin": np.searchsorted(np.asarray(e64["sub_len"]), sub_len,
                                     "right"),
            "ebin": np.searchsorted(
                np.asarray(e64["sub_entropy"]),
                np.asarray(ent, np.float32).astype(np.float64), "right"),
            "nlabels": np.asarray(nlab), "tld": np.asarray(tld)}
        consts["per_name"] = {n: jnp.asarray(v.astype(np.int32))
                              for n, v in per_name.items()}
        casts = [("client_u32", np.uint32), ("qname_codes", np.int32),
                 ("qtype", np.int32), ("rcode", np.int32),
                 ("frame_len", np.float32), ("hour", np.float32)]

        @jax.jit
        def block(c, client, codes, qtype, rcode, flen, hour):
            fields = {n: v[codes] for n, v in c["per_name"].items()}
            fields.update(qtype=qtype, rcode=rcode,
                          hbin=bins(c, "hour", hour),
                          flbin=bins(c, "frame_len", flen))
            return score(c, word_id(c, fields), doc_id(c, client))

        return casts, consts, block

    raise ValueError(f"no reference for datatype {config['datatype']!r}")


def all_scores(config: dict, model: dict, cols: dict, n_events: int,
               block: int = REF_BLOCK):
    """Every event's reference score, on the device, block by block."""
    import jax.numpy as jnp
    casts, consts, fn = make_scorer(config, model, cols)
    out = []
    for lo in range(0, n_events, block):
        hi = min(lo + block, n_events)
        args = [np.asarray(cols[name][lo:hi], dt) for name, dt in casts]
        if hi - lo < block:                 # one shape, one program
            args = [np.pad(a, (0, block - (hi - lo))) for a in args]
        out.append(fn(consts, *args)[:hi - lo])
    return jnp.concatenate(out)


def judge(scores, answer_idx: np.ndarray, answer_scores: np.ndarray,
          max_results: int) -> dict:
    """The program's answer for one chunk against the reference's scores
    of every event of it. `winner_gap`: the widest share by which a
    returned winner's reference score lies above the reference's
    `max_results`-th least score. `score_gap`: the widest share by which
    a returned score departs from the reference's for that event.
    `answer_mismatch` counts what is wrong outright: winners missing or
    too many, an index out of range or returned twice, an event that is
    not under tol, scores out of order."""
    import jax.numpy as jnp
    n = int(scores.shape[0])
    finite = int(jnp.isfinite(scores).sum())
    due = min(max_results, finite)
    keep = answer_idx >= 0
    idx, got = answer_idx[keep].astype(np.int64), answer_scores[keep]
    bad = abs(len(idx) - due) + int((idx >= n).sum())
    bad += len(idx) - len(np.unique(idx))
    bad += int((np.diff(got) < 0).sum())
    idx = np.minimum(idx, n - 1)
    ref = np.asarray(scores[jnp.asarray(idx.astype(np.int32))], np.float64)
    bad += int((~np.isfinite(ref)).sum())
    out = {"answer_mismatch": bad, "winner_gap": 0.0, "score_gap": 0.0,
           "n_due": due}
    ok = np.isfinite(ref)
    if due and ok.any():
        kth = float(jnp.sort(scores)[due - 1])
        out["winner_gap"] = float(np.maximum(ref[ok] / kth - 1.0, 0).max())
        out["score_gap"] = float(np.abs(got[ok] / ref[ok] - 1.0).max())
        out["kth_score"] = kth
    return out
