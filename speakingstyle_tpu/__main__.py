"""``python -m speakingstyle_tpu <command>`` dispatcher."""

import argparse
import sys

COMMANDS = (
    "train",
    "distill",
    "evaluate",
    "synthesize",
    "preprocess",
    "prepare_align",
    "train_vocoder",
    "vocode",
    "convert",
    "analyze",
    "serve",
    "replica",
)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    parser = argparse.ArgumentParser(prog="speakingstyle-tpu")
    sub = parser.add_subparsers(dest="command", required=True)
    import importlib

    modules = {}
    for name in COMMANDS:
        mod = importlib.import_module(f"speakingstyle_tpu.cli.{name}")
        modules[name] = mod
        mod.build_parser(sub.add_parser(name, help=mod.__doc__.splitlines()[0]))
    args = parser.parse_args(argv)
    # before any subcommand can compile: the persistent compile cache goes
    # where JAX_COMPILATION_CACHE_DIR points, else <checkout>/.jax_cache
    from speakingstyle_tpu.obs.jaxmon import enable_compilation_cache

    enable_compilation_cache()
    return modules[args.command].main(args)


if __name__ == "__main__":
    main()
