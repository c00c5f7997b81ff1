# Dev loops (reference parity: top-level Makefile + per-service Makefile.ci).

PY ?= python
TEST_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

IMAGE ?= seldon-core-tpu/platform:latest

.PHONY: lint test test-fast chip-smoke dryrun protos native install-bundle image release clean profile-smoke distill-smoke replica-smoke chaos-smoke kvtier-smoke

lint:  ## invariant linter (trace-safety / commit-point / registry-drift / phase-registry / ladder)
	$(PY) -m seldon_core_tpu.tools.lint

test: lint profile-smoke distill-smoke replica-smoke chaos-smoke kvtier-smoke  ## full suite on the 8-device virtual CPU mesh
	$(PY) -m pytest tests/ -q

profile-smoke:  ## short generative soak: the sampling profiler must capture >=1 stack AND the pipelined loop must hide host work (overlap_of_gap > 0)
	$(TEST_ENV) ENGINE_DECODE_PIPELINE=on $(PY) -m seldon_core_tpu.tools.soak --duration 3 --users 4 --prefix-share 0.5 --profile /tmp/decode_profile.folded

replica-smoke:  ## short replicated-decode soak: 2 replicas behind the affinity router — per-replica allocator audits green, aggregate prefix hit rate above the round-robin floor
	$(TEST_ENV) $(PY) -m seldon_core_tpu.tools.soak --duration 3 --users 4 --replicas 2

chaos-smoke:  ## seeded replica-kill mid-soak: induced allocator-OOM crashes one replica's loop under load — zero client errors, eviction + migration + half-open readmission asserted, allocator audits green
	$(TEST_ENV) $(PY) -m seldon_core_tpu.tools.soak --duration 6 --users 4 --replicas 2 --kill-replica 0@2

kvtier-smoke:  ## short KV-overflow soak: 2-entry device prefix index under an 8-group mix with a host tier below — demotions AND promotions must fire, allocator audit green, zero recompiles
	$(TEST_ENV) $(PY) -m seldon_core_tpu.tools.soak --duration 3 --users 4 --kv-overflow

distill-smoke:  ## tiny feature-draft distillation through the CLI (the pytest smoke asserts the accept delta + zoo round-trip)
	$(TEST_ENV) $(PY) -m seldon_core_tpu.training.distill_draft --features --vocab 128 --hidden 64 --layers 2 --ffn 128 --max-len 48 --seq 8 --horizon 24 --batch 8 --steps 30 --log-every 0 --out /tmp/draft_feat_smoke.npz

test-fast: lint  ## skip the slow model/parallel tests
	$(PY) -m pytest tests/ -q -x --ignore=tests/test_models_heavy.py --ignore=tests/test_parallel.py

chip-smoke:  ## both serving tiers over real HTTP on the attached chip, each checked against an independent forward (fails without one; --chips 4 for the cross-chip paths)
	$(PY) chip_smoke.py

dryrun:  ## compile-check the multichip path on 8 virtual devices
	$(TEST_ENV) $(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun ok')"

protos:  ## regenerate pb2 modules (protoc is in the base image)
	cd seldon_core_tpu/proto && protoc --python_out=. prediction.proto seldon_deployment.proto

native:  ## force-rebuild the C wire codec
	rm -f seldon_core_tpu/native/_fastcodec.so
	$(PY) -c "from seldon_core_tpu import native; assert native.available(); print('fastcodec ok')"

install-bundle:  ## render k8s manifests to deploy/rendered/
	$(PY) -m seldon_core_tpu.tools.install --with-redis --with-monitoring -o deploy/rendered

image:  ## build the platform image the install bundle deploys
	docker build -t $(IMAGE) .

release:  ## VERSION=x.y.z make release — bump + tag (push tags to publish via CI)
	$(PY) -m seldon_core_tpu.tools.release $(VERSION) --tag

clean:
	rm -rf .pytest_cache .jax_cache deploy/rendered seldon_core_tpu/native/_fastcodec.so*
	find . -name __pycache__ -type d -exec rm -rf {} +
