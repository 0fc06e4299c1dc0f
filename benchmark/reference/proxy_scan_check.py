"""Plain reference for the proxy day scan: raw columns in, every event's
score out, in straightforward `jax.numpy` at the precision the
configuration states (float32; the score table at the backend's default
matmul precision). It imports nothing of the program and takes none of
its tables. Like `scan_check.py` beside it, whose extension for the
unseen, sorted look-up, rank vocabulary and entropy it shares (and whose
`judge` judges its scores too), it is given the trained model as the fit
left it (theta, phi, the sorted vocabulary of packed word keys, the
sorted client addresses, the fitted edges and the fitted table of common
agents).

A proxy word, as `configs/proxy-k20.json` states it:
  hbin | uebin << 6 | ulbin << 12 | hostip << 18 | ua << 19 | cclass << 29
Once per unique string, on the host, in float64:
  ulbin   searchsorted(side=right) of len(uri) against `uri_len`;
  uebin   the same of the URI's character-level Shannon entropy against
          `uri_entropy`, the entropy rounded through float32 first;
  hostip  the host is a raw IPv4 address, ^\\d{1,3}(\\.\\d{1,3}){3}$;
  ua      the agent's index in the fitted sorted `ua_common`, else the
          one rare code (`ua_rare`, 1023).
Per event, on the device: cclass = respcode // 100, and hbin =
searchsorted(side=right) of the hour against the float32-cast edges.
The score is table[client document, word], kept if it is under tol; the
answer due is the `max_results` events of least score, ties to the
lower index (`scan_check.judge`).

Departures from the program, each on purpose:
- The program packs a compact 20-bit key of its own and finds it by a
  compare against the whole re-encoded vocabulary; the reference ranks
  each field among the values the vocabulary holds of it and searches
  the mixed-radix number (`scan_check._rank_vocab`). A value the
  vocabulary never saw in any one field makes the word unseen.
- The program calls a response code invalid when it is negative or its
  class is 8 or more, and gives it the key -1. The reference has no
  such rule and needs none: the class is looked up like any field, and
  no vocabulary the program accepts holds a negative class or one of 8
  or more (`build_proxy_tables` refuses it), so such an event takes the
  model's extra row here too. The host path's `PROXY_SPEC.pack` would
  mask the class to its 4 bits instead; neither the fused scan nor the
  reference does.
- The program computes all entropies at once from one code-point buffer
  in float64 and rounds to float32; the reference sums per string with
  `math.log2` and rounds the same way. The sums may differ in the last
  float64 bit, which the rounding removes except on a float32 boundary.
- The program looks addresses up by a sort-merge join and gathers the
  partial keys of all three dictionaries packed; the reference searches
  (`scan_check._lookup`) and gathers each field by itself.
"""

from __future__ import annotations

import re

import numpy as np

from benchmark.reference.scan_check import (REF_BLOCK, _entropy, _lookup,
                                            _rank_vocab, extend_for_unseen)

_IPV4 = re.compile(r"^\d{1,3}(\.\d{1,3}){3}$")
CASTS = [("client_u32", np.uint32), ("uri_codes", np.int32),
         ("host_codes", np.int32), ("ua_codes", np.int32),
         ("respcode", np.int32), ("hour", np.float32)]


def per_unique(config: dict, model: dict, cols: dict) -> dict:
    """The word's string fields per unique URI, host and agent."""
    edges = model["edges"]
    uris = [str(u) for u in cols["uris"]]
    entropy = np.asarray([_entropy(u) for u in uris],
                         np.float32).astype(np.float64)
    common = [str(a) for a in edges["ua_common"]]
    place = {a: i for i, a in enumerate(common)}
    assert common == sorted(common) and len(place) == len(common)
    return {
        "uri": {
            "ulbin": np.searchsorted(np.asarray(edges["uri_len"], np.float64),
                                     [float(len(u)) for u in uris], "right"),
            "uebin": np.searchsorted(
                np.asarray(edges["uri_entropy"], np.float64), entropy,
                "right")},
        "host": {"hostip": np.asarray(
            [int(bool(_IPV4.match(str(h)))) for h in cols["hosts"]])},
        "agent": {"ua": np.asarray(
            [place.get(str(a), int(config["ua_rare"]))
             for a in cols["agents"]])},
    }


def make_scorer(config: dict, model: dict, cols: dict):
    """Returns (consts, words_fn, block_fn): `words_fn(consts, uri_codes,
    host_codes, ua_codes, respcode, hour)` gives each event's word id in
    the extended vocabulary, `block_fn(consts, client, ...)` its score
    (inf where the event is not under tol). The tables ride in `consts`
    as arguments, so the compiled block holds none of them."""
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
        "hour_edges": jnp.asarray(
            np.asarray(model["edges"]["hour"], np.float32).ravel()),
        "uniq": {n: jnp.asarray(u) for n, u in uniq.items()},
        "key_sorted": jnp.asarray(key_sorted),
        "ids_sorted": jnp.asarray(
            np.asarray(model["word_key_ids"], np.int32)[order]),
        "per": {d: {n: jnp.asarray(np.asarray(v, np.int32))
                    for n, v in fields.items()}
                for d, fields in per_unique(config, model, cols).items()},
    }

    def words(c, uri_c, host_c, ua_c, respcode, hour):
        fields = {n: v[uri_c] for n, v in c["per"]["uri"].items()}
        fields.update({n: v[host_c] for n, v in c["per"]["host"].items()})
        fields.update({n: v[ua_c] for n, v in c["per"]["agent"].items()})
        fields["cclass"] = respcode // 100
        fields["hbin"] = jnp.searchsorted(c["hour_edges"], hour,
                                          side="right").astype(jnp.int32)
        # Each field to its rank among the vocabulary's values of that
        # field; a value the vocabulary never saw makes the word unseen.
        key, hit = jnp.int32(0), True
        for n, _ in layout:
            u, v = c["uniq"][n], fields[n].astype(jnp.int32)
            pos = jnp.clip(jnp.searchsorted(u, v), 0, u.shape[0] - 1)
            hit = hit & (u[pos] == v)
            key = key * jnp.int32(u.shape[0]) + pos
        wid = _lookup(c["key_sorted"], c["ids_sorted"], key, unseen_w)
        return jnp.where(hit, wid, jnp.int32(unseen_w))

    def block(c, client, uri_c, host_c, ua_c, respcode, hour):
        wid = words(c, uri_c, host_c, ua_c, respcode, hour)
        did = _lookup(c["doc_sorted"], c["doc_ids"], client, unseen_d)
        s = c["table"][did * jnp.int32(v_x) + wid]
        return jnp.where(s < tol, s, jnp.inf)

    return consts, jax.jit(words), jax.jit(block)


def _blocks(cols: dict, n_events: int, block: int, casts):
    """The staged columns block by block, the last padded to the one
    shape (one program), with each block's true length."""
    for lo in range(0, n_events, block):
        hi = min(lo + block, n_events)
        args = [np.asarray(cols[name][lo:hi], dt) for name, dt in casts]
        if hi - lo < block:
            args = [np.pad(a, (0, block - (hi - lo))) for a in args]
        yield args, hi - lo


def all_scores(config: dict, model: dict, cols: dict, n_events: int,
               block: int = REF_BLOCK):
    """Every event's reference score, on the device, block by block."""
    import jax.numpy as jnp
    consts, _, fn = make_scorer(config, model, cols)
    return jnp.concatenate([fn(consts, *args)[:m] for args, m in
                            _blocks(cols, n_events, block, CASTS)])


def word_ids(config: dict, model: dict, cols: dict, n_events: int,
             block: int = REF_BLOCK) -> np.ndarray:
    """Every event's word id in the extended vocabulary (the last id is
    the unseen word's), for the tests that hold the reference's words
    against the program's host path."""
    consts, fn, _ = make_scorer(config, model, cols)
    return np.concatenate([np.asarray(fn(consts, *args))[:m] for args, m in
                           _blocks(cols, n_events, block, CASTS[1:])])
