"""Room for a trace's frames on the interpreter's frame stack.

CPython (3.11 on) keeps a thread's Python frames in chunks of 16 KiB and
frees a chunk, back to the operating system, when the first frame in it
returns. A loop whose calls cross a chunk's edge therefore maps and unmaps
a chunk on EVERY call. Tracing a program is such a loop, thousands of
small calls some hundreds of frames deep (a Pallas kernel's unrolled copy
loop traces a ``cond`` and two descriptors a page), and where the edge
falls is decided by the bytes of every frame above it: an edit anywhere
between ``main`` and the kernel moves it. On the TPU host the code cell's
decode step traced in 2.2 s or in 3.5 s with the same text coming out, by
that alone (PERF.md section 6, PR 47); a page fault is cheap on a
workstation and dear on a virtual host.

``on_one_stack_chunk(fn)`` gives ``fn``'s own frame so large a stack that
the interpreter opens a chunk for it in which every deeper frame fits: the
edges below it are gone, wherever the caller stood. The room is address
space, not memory (only the pages that frames touch are ever backed), it
is asked for when ``fn`` is entered and returned when it leaves, so wrap
what runs when a program is TRACED (the function handed to ``jax.jit``),
never what runs every step.
"""

import functools

# a frame of 2**15 slots is 256 KiB: the interpreter doubles a chunk until
# the frame fits with its overhead, to 512 KiB, and the half that is left
# holds some 1,500 frames where the recursion limit is 1,000
_SLOTS = 1 << 15


def on_one_stack_chunk(fn):
    """``fn`` behind a frame that opens a chunk of the frame stack for
    itself and everything it calls; name, signature and defaults are
    ``fn``'s (``jax.jit`` names a program and finds its arguments through
    them)."""

    @functools.wraps(fn)
    def on_its_own_chunk(*args, **kwargs):
        return fn(*args, **kwargs)

    on_its_own_chunk.__code__ = on_its_own_chunk.__code__.replace(
        co_stacksize=_SLOTS)
    return on_its_own_chunk
