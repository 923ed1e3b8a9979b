from repro.compile_cache import use_persistent_cache
from repro.union.cli import main

if __name__ == "__main__":
    use_persistent_cache()
    main()
