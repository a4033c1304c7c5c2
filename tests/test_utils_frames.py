"""``utils/frames.on_one_stack_chunk``: a traced function behind a frame
that opens a chunk of the interpreter's frame stack for everything below
it (why: the module's docstring; PERF.md section 6, PR 47)."""

import inspect
import resource

import jax
import jax.numpy as jnp
import pytest

from deeperspeed_tpu.utils.frames import on_one_stack_chunk


def faults():
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


def under(n, fn):
    """``fn()`` with ``n`` more frames above it."""
    return under(n - 1, fn) if n else fn()


def many_short_recursions():
    def down(d):
        return d and down(d - 1)

    for _ in range(2000):
        down(12)


def test_the_wrapped_function_keeps_its_name_signature_and_defaults():
    def ds_program(params, pool, kc_pool=None, *, flag=False):
        """doc"""
        return params, pool, kc_pool, flag

    wrapped = on_one_stack_chunk(ds_program)
    assert wrapped.__name__ == "ds_program" and wrapped.__doc__ == "doc"
    assert inspect.signature(wrapped) == inspect.signature(ds_program)
    assert wrapped(1, 2) == (1, 2, None, False)
    assert wrapped(1, 2, 3, flag=True) == (1, 2, 3, True)
    # the room is its frame's, asked for when it is entered
    assert wrapped.__code__.co_stacksize >= 1 << 15
    assert ds_program.__code__.co_stacksize < 64


def test_jit_names_the_program_and_donates_through_it():
    def ds_program(x, pool, scale=None):
        return x * 2 if scale is None else x * scale, pool + 1

    x, pool = jnp.arange(4.0), jnp.zeros((8,))
    plain = jax.jit(ds_program, donate_argnums=(1,))
    wrapped = jax.jit(on_one_stack_chunk(ds_program), donate_argnums=(1,))
    # the same program under the same name, the same argument given away
    assert str(jax.make_jaxpr(plain)(x, pool)) \
        == str(jax.make_jaxpr(wrapped)(x, pool))
    assert "name=ds_program" in str(jax.make_jaxpr(wrapped)(x, pool))
    assert plain.lower(x, pool).as_text() == wrapped.lower(x, pool).as_text()
    out, kept = wrapped(x, pool)
    assert out.tolist() == [0.0, 2.0, 4.0, 6.0] and pool.is_deleted()
    assert wrapped(x, kept, 3.0)[0].tolist() == [0.0, 3.0, 6.0, 9.0]


def test_a_loop_across_a_chunks_edge_stops_mapping_and_unmapping():
    """At SOME depth of the caller a loop of short calls crosses the edge
    of a 16 KiB chunk of the frame stack, and every crossing is a chunk
    mapped, touched and unmapped: thousands of page faults. Behind the
    wrapper the same loop at the same depth makes none."""
    worst = (0, 0)
    for depth in range(0, 400, 8):
        before = faults()
        under(depth, many_short_recursions)
        worst = max(worst, (faults() - before, depth))
    crossings, depth = worst
    if crossings < 1000:
        pytest.skip("this interpreter keeps its frames another way: no "
                    f"depth of 50 made the loop fault ({crossings} at most)")
    before = faults()
    under(depth, on_one_stack_chunk(many_short_recursions))
    assert faults() - before < 50, (crossings, depth)
