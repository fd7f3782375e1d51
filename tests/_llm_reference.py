"""The reference the serving-engine tests hold an engine to: greedy
tokens from the full forward pass (``gpt2.forward`` over the whole
sequence, one new token a pass). It shares no code with any engine loop,
pool or decode program, so an engine test compares the engine with the
model and never with another engine."""


def greedy_reference(cfg, params, prompt, n):
    """One full forward a token over the sequence so far, right-padded
    to the context so that every pass is one compiled shape (attention
    is causal: what stands after a position cannot reach it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt2

    forward = jax.jit(lambda p, t: gpt2.forward(p, t, cfg))
    seq = list(prompt)
    out = []
    for _ in range(n):
        tok = np.zeros((1, cfg.n_positions), np.int32)
        tok[0, : len(seq)] = seq
        logits = forward(params, jnp.array(tok))
        nxt = int(jnp.argmax(logits[0, len(seq) - 1, : cfg.vocab_size]))
        out.append(nxt)
        seq.append(nxt)
    return out


def engine_reference(srv, prompt, max_new):
    """What engine ``srv`` must answer to ``prompt`` at temperature 0:
    the reference's tokens for as many steps as the context allows (the
    engine keeps the last ``n_positions - 1`` prompt tokens and stops
    when the context is full)."""
    t_max = srv.model_cfg.n_positions
    prompt = list(prompt)[-(t_max - 1):]
    n = min(max_new, t_max - len(prompt))
    return greedy_reference(srv.model_cfg, srv.params, prompt, n)
