"""Most pages of the pool in use (live lanes and cached prefixes) at any
reading of engine.stats() during the window, over n_pages. A full pool preempts lanes and evicts prefixes, which
stretches both the first token's wait and the gaps between tokens; the
cell's one end-to-end tail is the latter (PERF.md, section 2).
"""
LAYER = "KV page manager"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_mean_ms"


def read(run):
    polls = [p["pages_used"] for p in run.get("polls") or []
             if "pages_used" in p]
    polls.append(run["stats_after"].get("pages_used", 0))
    n = run["stats_after"].get("n_pages")
    return 100.0 * max(polls) / n if n else None
