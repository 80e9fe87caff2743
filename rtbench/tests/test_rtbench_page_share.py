"""The ``decode_page_share`` reader: the serve's count where the
program keeps one, nothing where it does not."""

from types import SimpleNamespace

from rtbench import harness


def test_decode_page_share_reads_the_serve_or_nothing():
    read = harness.reader("decode_page_share").read
    assert read(SimpleNamespace(res={"decode_page_share": 0.18})) == 0.18
    assert read(SimpleNamespace(res={"n_tasks": 4})) is None
