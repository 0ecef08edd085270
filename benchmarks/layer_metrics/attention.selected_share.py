"""Layer: indexer.  Share, %, of the context tokens that the attention
read: window deltas of the program's counters
`generation.dsa_tokens_attended` (each row's selected tokens, summed
over rows and layers) over `generation.dsa_context_tokens` (the tokens
of the same rows' contexts, likewise).  100 where every context fits
the selection; index_topk over the context where it is longer.  None
from a program without the counters."""


def read(obs):
    counters = obs["result"].get("counters") or {}
    attended = counters.get("generation.dsa_tokens_attended")
    context = counters.get("generation.dsa_context_tokens")
    if attended is None or not context:
        return None
    return 100.0 * attended / context
