"""Tiny sizes for the CPU rehearsal of each cell (control flow only; the
result is marked ``rehearsal`` and never printed as a contract line)."""

TINY = {
    "serve-mistral7b-chat-steady": {
        "config": {"hidden_size": 64, "intermediate_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "num_hidden_layers": 2, "vocab_size": 256,
                   "max_position_embeddings": 512, "sliding_window": 512,
                   "serve": {"block_size": 16, "token_budget": 64,
                             "max_ragged_sequence_count": 4,
                             "max_context": 256, "kv_pool_blocks": 80,
                             "check_prompt_tokens": 40,
                             "check_decode_tokens": 3}},
        "traffic": {"arrivals": {"rate_per_s": 4.0},
                    "prompt_tokens": {"median": 24, "min": 4, "max": 100},
                    "output_tokens": {"median": 6, "min": 2, "max": 12},
                    "preroll_s": 1.0, "drain_s": 20.0, "trace_seconds": 1.0}},
    "serve-mistral7b-longprompt-closed": {
        "config": {"hidden_size": 64, "intermediate_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "num_hidden_layers": 2, "vocab_size": 256,
                   "max_position_embeddings": 512, "sliding_window": 512,
                   "serve": {"block_size": 16, "token_budget": 64,
                             "max_ragged_sequence_count": 4,
                             "max_context": 256, "kv_pool_blocks": 80,
                             "check_prompt_tokens": 40,
                             "check_decode_tokens": 3}},
        "traffic": {"clients": 2,
                    "prompt_tokens": {"median": 60, "min": 20, "max": 200},
                    "output_tokens": {"min": 2, "max": 6},
                    "preroll_s": 1.0, "drain_s": 20.0, "trace_seconds": 1.0}},
    "train-mistral7b-z3tp-s4k": {
        "config": {"hidden_size": 64, "intermediate_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "num_hidden_layers": 2, "vocab_size": 256,
                   "max_position_embeddings": 512, "sliding_window": 512},
        "traffic": {"seq_len": 128, "trace_steps": 2}},
    "train-gpt2large-d64-s1k": {
        "config": {"n_embd": 64, "n_head": 4, "n_layer": 2,
                   "n_positions": 128, "vocab_size": 256},
        "traffic": {"seq_len": 128, "trace_steps": 2}},
}
