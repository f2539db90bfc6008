"""Share of the window's tokens that decode bursts emitted."""
import window


def read(spec, ctx):
    tokens = window.counter_by(ctx, "mxtpu_generate_tokens", "path")
    if not tokens or sum(tokens.values()) <= 0:
        return None
    return 100.0 * tokens.get("burst", 0.0) / sum(tokens.values())
