# Top-level driver, mirroring the reference's build UX (its Makefile
# produces bin/cxxnet; here the "binary" is `python -m cxxnet_tpu` and
# native code lives in native/).
#
#   make            - build the native IO runtime (libcxxnet_native.so)
#   make wrapper    - C ABI library + demo + native im2bin
#   make test       - full pytest suite (virtual 8-device CPU mesh)
#   make clean

all: native

native:
	$(MAKE) -C native

wrapper:
	$(MAKE) -C native wrapper demo im2bin

test:
	python -m pytest tests/ -q

# dev loop: skips the multi-process spawns, the reference-conf CLI
# end-to-end runs, and the C-ABI/embedded-interpreter tests (the
# compile-heavy tail); run `make test` before a PR
test-fast:
	python -m pytest tests/ -q --ignore=tests/test_multihost.py 		--ignore=tests/test_reference_configs.py 		--ignore=tests/test_capi.py

clean:
	$(MAKE) -C native clean

.PHONY: all native wrapper test test-fast clean
