"""Prompt tokens the radix tree spared from prefill, over the prompt
tokens of the requests admitted: `prefix_matched` and `prompt_tokens` of
the program's `decode` spans, requests that entered in the window."""

NAME, UNIT, BETTER = "prefix_saved_share", "%", "higher"
LAYER, MOVES, SOURCE = ("KV cache manager", "serve_tokens_per_s",
                        "program_span")


def read(run):
    spans = [s.get("attrs", {}) for t in run.traces for s in t["spans"]
             if s["name"] == "decode"]
    prompt = sum(a.get("prompt_tokens", 0) for a in spans)
    if not prompt:
        return None
    return 100.0 * sum(a.get("prefix_matched", 0) for a in spans) / prompt
