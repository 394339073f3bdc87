"""Build the native host-ops library: python -m transferia_tpu.native.build"""

from transferia_tpu.native import build

if __name__ == "__main__":
    print(f"built {build(force=True)}")
