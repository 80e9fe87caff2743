"""Pages the paged decode kernel walked over the pages it could walk
(every slot's whole block table), meaned over the window's decode
launches: the serve's ``decode_page_share`` (``serving/engine.py``).
A program without the count reports nothing."""


def read(run):
    return run.res.get("decode_page_share")
