"""Paper Fig 7: full-precision CNN training (fwd+bwd) — PIM vs GPU/TPU."""

from __future__ import annotations

from .fig6_cnn_infer import run as _run


def run() -> list[dict]:
    return _run(train=True)


def main():
    from repro import compile_cache

    from .common import emit

    compile_cache.enable()
    emit(run())


if __name__ == "__main__":
    main()
