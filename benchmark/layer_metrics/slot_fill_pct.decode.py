"""Share of slot-steps that yielded a token: ``tokens_out`` over
(``prefill_steps`` + ``decode_steps``) x ``slots``, deltas of
``DecodeEngine.stats()`` over the window.  A prefill step stalls every slot
for one token, so serial admission shows here."""

NAME = "slot_fill_pct.decode"
UNIT = "%"
LAYER = "decode engine"
MOVES = "decode_tokens_per_s"


def read(facts):
    c = facts.get("counts")
    if not c:
        return None
    steps = c["prefill_steps"] + c["decode_steps"]
    return 100.0 * c["tokens_out"] / (steps * facts["slots"]) if steps else None
