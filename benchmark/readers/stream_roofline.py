"""The stream's E-step as a share of the chip's memory roofline, in
percent. The work is counted from shapes and from the passes the
program says it ran, never from what implements them:

  one token in one pass of the E-step reads its document's row of
  E[log theta] and its word's row of E[log beta] (K float32 each),
  reads and writes back its document's row of the new gamma (2 K
  float32), and streams its document id and its word id (8 B):
  16 K + 8 bytes, 328 at K = 20.

The token passes of a superstep are the driver's, from the program's own
counts (`run["window"]["token_passes_by_call"]`: warm passes x the
batch's real tokens + extended passes x the active tokens, padding
left out, so the share reads low, never high). `"over": "scope"` divides
the least time by the device time under `spec["scope"]` in the whole
executions of the program named by `spec["module_match"]` inside the
traced window (`estep_roofline`); `"over": "window"` by the traced
window's wall time (`stream_mfu`: the same work as a share of the whole
step, idle time, the other scopes and every other program included).
Nothing to read gives nothing, never 0."""


def estep_bytes_per_token_pass(n_topics: int) -> float:
    return 16.0 * n_topics + 8.0


def read(run: dict, spec: dict):
    red, peaks = run.get("trace_summary"), run.get("peaks")
    passes = run.get("window", {}).get("token_passes_by_call")
    if not red or not peaks or not passes:
        return None
    scopes = run["manifest"].load("readers", "scope_seconds")
    if scopes.read(run, {"scope": "onix.", "module_match":
                         spec["module_match"]}) is None:
        return None
    by_scope, whole = scopes.book(run["scope_planes"], spec["module_match"])
    calls = int(round(whole))
    if calls < 1 or calls > len(passes):
        return None
    least = (estep_bytes_per_token_pass(run["config"]["n_topics"])
             * sum(passes[:calls]) / peaks["hbm_bytes_per_s"])
    if spec["over"] == "window":
        seconds = red["window_s"]
    else:
        seconds = sum(v for k, v in by_scope.items()
                      if k.startswith(spec["scope"]))
    return 100.0 * least / seconds if seconds > 0 else None
