# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="include the large CNNs (slow on CPU)")
    ap.add_argument("--skip", default="", help="comma-separated bench groups to skip")
    args = ap.parse_args()
    skip = set(filter(None, args.skip.split(",")))

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        ablation_removal,
        kernel_bench,
        roofline_summary,
        serve_continuous,
        table_v,
        table_vi_vii,
        table_viii,
    )

    groups = [
        ("table_v", lambda: table_v.run()),
        ("table_vi_vii", lambda: table_vi_vii.run()),
        ("ablation", lambda: ablation_removal.run()),
        ("kernel", lambda: kernel_bench.run()),
        ("serve_continuous", lambda: serve_continuous.run()),
        ("table_viii", lambda: table_viii.run(full=args.full)),
        ("roofline", lambda: roofline_summary.run()),
    ]
    print("name,us_per_call,derived")
    failed = False
    for name, fn in groups:
        if name in skip:
            continue
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.1f},{derived}")
        except Exception as e:  # noqa: BLE001
            failed = True
            print(f"{name},0,ERROR: {e!r}", file=sys.stderr)
            print(f"{name},0,ERROR: {e!r}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
