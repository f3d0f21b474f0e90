# Convenience targets for the native components and tests.

NATIVE_DIR := src/cpp/monitoring
NATIVE_BUILD := $(NATIVE_DIR)/build
# Release leg: -DNDEBUG must not compile the checks out (round-4
# regression: assert-based tests segfaulted under Release).
NATIVE_BUILD_REL := $(NATIVE_DIR)/build_rel

.PHONY: native native-release native-test test lint all clean

all: native

native:
	cmake -B $(NATIVE_BUILD) -G Ninja $(NATIVE_DIR)
	cmake --build $(NATIVE_BUILD)

native-release:
	cmake -B $(NATIVE_BUILD_REL) -G Ninja \
	  -DCMAKE_BUILD_TYPE=Release $(NATIVE_DIR)
	cmake --build $(NATIVE_BUILD_REL)

native-test: native native-release
	$(NATIVE_BUILD)/monitoring_test
	$(NATIVE_BUILD_REL)/monitoring_test

test: native-test
	python -m pytest tests/ -q

# The same two analysis layers CI's `analysis` job gates on: ruff for
# generic pyflakes/bugbear classes, graftlint --strict for the domain
# rules (GL001-GL009). Run before pushing; pre-commit hooks run the
# identical pair (see .pre-commit-config.yaml).
lint:
	ruff check cloud_tpu examples
	python -m cloud_tpu.analysis.lint cloud_tpu examples tests --strict

clean:
	rm -rf $(NATIVE_BUILD) $(NATIVE_BUILD_REL)
