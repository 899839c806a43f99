"""KV memory + tiers: prefill tokens the backend computed in the window
(``prefill_tokens_computed``) over the prompt tokens of the turns
admitted in it. Tokens served from a pin, a shared preamble or a
host-tier restore are not computed."""


def read(v):
    prompt = sum(r.prompt_len for r in v.driver.turns.values()
                 if v.w0 <= r.admitted < v.w1)
    if not prompt:
        return None
    return (v.c1["prefill_tokens"] - v.c0["prefill_tokens"]) / prompt
