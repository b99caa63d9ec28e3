"""The plain reference the serving engine's tokens are compared with: greedy
decoding by the whole-sequence forward (``LlamaModel.apply``, causal attention
over the sequence).  No cache, no paging, none of the engine's programs."""

import numpy as np


def greedy_reference(model, params, prompt, n_new):
    """The ``n_new`` tokens that follow ``prompt`` (a list of ids), each the
    argmax over the real vocabulary of the logits at the last position so far.

    The sequence lives in a buffer of its final length, so that one shape
    compiles instead of one a token: a causal forward's logits at a position
    do not see what follows it, so the zeros behind the last token are inert.
    """
    import jax

    forward = jax.jit(model.apply)
    n = len(prompt)
    seq = np.zeros((1, n + n_new), np.int32)
    seq[0, :n] = prompt
    for i in range(n, n + n_new):
        logits = np.asarray(forward(params, seq)[0, i - 1], np.float32)
        # ids at or above vocab_size pad the projection and are never tokens
        seq[0, i] = int(np.argmax(logits[: model.config.vocab_size]))
    return seq[0, n:].tolist()


def interleaved_tables(slots, pages_per_slot):
    """Page tables [slots, pages_per_slot] that deal the physical pages to
    the slots in reverse and interleaved, so a program that ignored the page
    table would read another slot's rows."""
    pages = np.arange(slots * pages_per_slot, dtype=np.int32)[::-1]
    return np.ascontiguousarray(pages.reshape(pages_per_slot, slots).T)


def paged_greedy(llm, prompts, n_new, *, page_size, chunk, pages_per_slot=None):
    """The same tokens from ``llm.engine_programs`` driven by hand, as the
    engine's thread drives them: one slot a prompt, each prompt prefilled in
    chunks of ``chunk``, then ``n_new - 1`` decode steps over all slots at
    once, through ``interleaved_tables``.  A slot's table holds the longest sequence, or ``pages_per_slot`` pages.
    Each program must have compiled exactly once by the end."""
    slots = len(prompts)
    needed = -(-(max(map(len, prompts)) + n_new) // page_size)
    pages_per_slot = pages_per_slot or needed
    assert pages_per_slot >= needed
    tables = interleaved_tables(slots, pages_per_slot)
    programs = llm.engine_programs(num_pages=slots * pages_per_slot, page_size=page_size)
    pages = programs["init"]()
    outs = []
    for slot, prompt in enumerate(prompts):
        for start in range(0, len(prompt), chunk):
            part = prompt[start : start + chunk]
            padded = np.zeros(chunk, np.int32)
            padded[: len(part)] = part
            first, pages = programs["prefill"](
                llm.params, pages, tables[slot], padded, np.int32(start), np.int32(len(part))
            )
        outs.append([int(first)])
    for _ in range(n_new - 1):
        tokens = np.asarray([o[-1] for o in outs], np.int32)
        positions = np.asarray([len(p) + len(o) - 1 for p, o in zip(prompts, outs)], np.int32)
        nxt, pages = programs["decode"](
            llm.params, pages, tables, tokens, positions, np.ones(slots, bool)
        )
        for o, t in zip(outs, np.asarray(nxt)):
            o.append(int(t))
    assert programs["prefill"]._cache_size() == programs["decode"]._cache_size() == 1
    return outs
