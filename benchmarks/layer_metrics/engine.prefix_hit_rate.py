"""Layer: scheduler.  Prompt tokens served from the prefix cache over
prompt tokens admitted (cache hits + tokens prefilled), %, from the
program's counters over the window.  A hit takes chunk rows out of the
steps that other requests' tokens wait for, and most of all it shortens the
time to first token (`engine.ttft_ms_p50`)."""


def read(obs):
    c = obs["result"].get("counters", {})
    hit = c.get("generation.prefix_cache_hit_tokens", 0)
    total = hit + c.get("generation.prefill_tokens_total", 0)
    return None if not total else 100.0 * hit / total
