"""What the harness tells the TPU runtime before JAX loads it (PR 45).

libtpu pins ``TPU_PREMAPPED_BUFFER_SIZE`` bytes of host memory inside
the first look at the device, 4 GiB by default: on a host without
transparent hugepages that was 5-19 s of every run's ``setup_s``, and
the part of it that moved from run to run (PERF.md, section 6).
"""

import pytest

from benchmark.harness import run


@pytest.mark.parametrize("given, kept", [
    ({}, str(256 << 20)),
    ({"TPU_PREMAPPED_BUFFER_SIZE": "4294967296"}, "4294967296")])
def test_the_premapped_buffer_is_bounded_unless_given(given, kept):
    environ = dict(given)
    run.runtime_env(environ)
    assert environ == {"TPU_PREMAPPED_BUFFER_SIZE": kept}
    assert int(kept) % (1 << 20) == 0


def test_main_sets_it_before_jax_is_loaded():
    """``main`` calls ``runtime_env`` ahead of its first import of jax:
    libtpu reads its environment once, when the backend starts."""
    import inspect

    body = inspect.getsource(run.main)
    assert 0 < body.index("runtime_env()") < body.index("import jax")
