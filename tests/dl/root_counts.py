"""The counts on a ``CausalLM`` transform's root span, by family, named
by the family's tiny rehearsal config
(``benchmark/rehearsal/configs/tiny_*.json``): what the stage counts
itself, and what it sums of the model's ``HybridLM.counts``
(``expert_pairs`` also as ``expert_pairs_max``). Each family's own test
file holds a transform's root to its family's set."""

STAGE_COUNTS = {"new_tokens", "state_bytes", "cache_bytes", "length_rung",
                "prefill_visits", "prefill_visits_run"}
_LATENT_COUNTS = STAGE_COUNTS | {
    "cache_positions", "cache_capacity", "expert_pairs", "expert_pairs_max",
    "dropped_pairs"}
ROOT_COUNTS = {
    "tiny_brumby": STAGE_COUNTS,
    "tiny_gigachat": _LATENT_COUNTS,
    "tiny_kimi": _LATENT_COUNTS,
    "tiny_xing": _LATENT_COUNTS | {"hc_sublayer_tokens"},
    "tiny_motif": _LATENT_COUNTS | {"hc_sublayer_tokens", "window_positions"},
}
