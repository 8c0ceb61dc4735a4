// Assert-based tests for the native core (no gtest in this image).
// Mirrors the reference's C++ test coverage (actorpool_test.cc: queue
// construct/close/enqueue/dequeue semantics; nest_serialize_test.cc:
// codec roundtrips) plus batcher promise semantics and a threaded stress.

#include <atomic>
#include <cassert>
#include <cstdio>
#include <cstring>
#include <set>
#include <thread>
#include <vector>

#include <sys/socket.h>

#include "actor_pool.h"
#include "array.h"
#include "client.h"
#include "clock.h"
#include "env_server.h"
#include "nest.h"
#include "queues.h"
#include "routing.h"
#include "shm.h"
#include "wire.h"

using namespace tbt;

#define CHECK(cond)                                                         \
  do {                                                                      \
    if (!(cond)) {                                                          \
      std::fprintf(stderr, "CHECK failed at %s:%d: %s\n", __FILE__,         \
                   __LINE__, #cond);                                        \
      std::abort();                                                         \
    }                                                                       \
  } while (0)

#define CHECK_THROWS(expr, ExceptionType)                                   \
  do {                                                                      \
    bool caught = false;                                                    \
    try {                                                                   \
      expr;                                                                 \
    } catch (const ExceptionType&) {                                        \
      caught = true;                                                        \
    }                                                                       \
    CHECK(caught);                                                          \
  } while (0)

static Array make_array(DType dtype, std::vector<int64_t> shape,
                        int64_t fill) {
  Array a(dtype, shape);
  if (dtype == DType::kI64) {
    int64_t* p = reinterpret_cast<int64_t*>(a.mutable_data());
    for (int64_t i = 0; i < a.numel(); ++i) p[i] = fill;
  } else if (dtype == DType::kF32) {
    float* p = reinterpret_cast<float*>(a.mutable_data());
    for (int64_t i = 0; i < a.numel(); ++i) p[i] = static_cast<float>(fill);
  } else {
    std::memset(a.mutable_data(), static_cast<int>(fill), a.nbytes());
  }
  return a;
}

static void test_array_concat_slice() {
  Array a = make_array(DType::kI64, {1, 2}, 1);
  Array b = make_array(DType::kI64, {1, 2}, 2);
  Array cat0 = concatenate({a, b}, 0);
  CHECK(cat0.shape() == (std::vector<int64_t>{2, 2}));
  const int64_t* p = reinterpret_cast<const int64_t*>(cat0.data());
  CHECK(p[0] == 1 && p[1] == 1 && p[2] == 2 && p[3] == 2);

  Array cat1 = concatenate({a, b}, 1);
  CHECK(cat1.shape() == (std::vector<int64_t>{1, 4}));
  p = reinterpret_cast<const int64_t*>(cat1.data());
  CHECK(p[0] == 1 && p[1] == 1 && p[2] == 2 && p[3] == 2);

  Array s = slice(cat0, 0, 1, 1);
  CHECK(s.shape() == (std::vector<int64_t>{1, 2}));
  CHECK(reinterpret_cast<const int64_t*>(s.data())[0] == 2);

  Array s1 = slice(cat1, 1, 1, 2);
  CHECK(s1.shape() == (std::vector<int64_t>{1, 2}));
  p = reinterpret_cast<const int64_t*>(s1.data());
  CHECK(p[0] == 1 && p[1] == 2);

  CHECK_THROWS(concatenate({a, make_array(DType::kF32, {1, 2}, 0)}, 0),
               std::invalid_argument);
  std::printf("array concat/slice ok\n");
}

static void test_nest_ops() {
  ArrayNest::Dict d;
  d.emplace("x", ArrayNest(make_array(DType::kI64, {2}, 5)));
  d.emplace("y", ArrayNest(ArrayNest::List{
                     ArrayNest(make_array(DType::kI64, {1}, 7))}));
  ArrayNest nest(d);

  CHECK(!nest.empty());
  CHECK(nest.front().dim(0) == 2);
  CHECK(nest.flatten().size() == 2);

  ArrayNest doubled = nest.map([](const Array& a) {
    Array out = a.clone();
    int64_t* p = reinterpret_cast<int64_t*>(out.mutable_data());
    for (int64_t i = 0; i < out.numel(); ++i) p[i] *= 2;
    return out;
  });
  CHECK(reinterpret_cast<const int64_t*>(doubled.front().data())[0] == 10);

  // pack_as roundtrip
  auto flat = doubled.flatten();
  ArrayNest packed = nest.pack_as(flat);
  CHECK(reinterpret_cast<const int64_t*>(packed.front().data())[0] == 10);
  CHECK_THROWS(nest.pack_as(std::vector<Array>{}), std::invalid_argument);

  // map2 structure mismatch
  CHECK_THROWS(
      ArrayNest::map2([](const Array& a, const Array&) { return a; }, nest,
                      ArrayNest(make_array(DType::kI64, {1}, 0))),
      std::invalid_argument);
  std::printf("nest ops ok\n");
}

static void test_wire_roundtrip() {
  wire::ValueNest::Dict msg;
  msg.emplace("type", wire::ValueNest(wire::Value::of_string("step")));
  msg.emplace("reward", wire::ValueNest(wire::Value::of(
                            make_array(DType::kF32, {}, 3))));
  msg.emplace("frame", wire::ValueNest(wire::Value::of(
                           make_array(DType::kU8, {2, 2, 1}, 9))));
  wire::ValueNest::List lst;
  lst.push_back(wire::ValueNest(wire::Value::of_int(-42)));
  lst.push_back(wire::ValueNest(wire::Value{}));
  msg.emplace("extras", wire::ValueNest(std::move(lst)));

  std::vector<uint8_t> framed = wire::encode(wire::ValueNest(msg));
  uint32_t length = framed[0] | (framed[1] << 8) | (framed[2] << 16) |
                    (framed[3] << 24);
  CHECK(length == framed.size() - 4);

  auto payload = std::make_shared<std::vector<uint8_t>>(framed.begin() + 4,
                                                        framed.end());
  wire::ValueNest out =
      wire::decode(payload->data(), payload->size(), payload);
  const auto& dict = out.dict();
  CHECK(dict.at("type").leaf().s == "step");
  const Array& frame = dict.at("frame").leaf().array;
  CHECK(frame.shape() == (std::vector<int64_t>{2, 2, 1}));
  CHECK(frame.data()[0] == 9);
  const Array& reward = dict.at("reward").leaf().array;
  CHECK(reward.ndim() == 0);  // 0-d survives (the Python-side regression)
  CHECK(dict.at("extras").list()[0].leaf().i == -42);
  CHECK(dict.at("extras").list()[1].leaf().kind ==
        wire::Value::Kind::kNone);

  // Truncated payload raises.
  CHECK_THROWS(wire::decode(payload->data(), payload->size() - 1, payload),
               wire::WireError);
  std::printf("wire roundtrip ok\n");
}

// Adversarial frames: the decoder sees untrusted bytes straight off a TCP
// socket, so dimension fields that would wrap the size computation must be
// rejected, not used to index out of bounds.
static void test_wire_malformed() {
  auto decode_bytes = [](std::vector<uint8_t> bytes) {
    auto payload = std::make_shared<std::vector<uint8_t>>(std::move(bytes));
    return wire::decode(payload->data(), payload->size(), payload);
  };
  auto put_i64 = [](std::vector<uint8_t>* buf, int64_t x) {
    for (int i = 0; i < 8; ++i)
      buf->push_back((static_cast<uint64_t>(x) >> (8 * i)) & 0xff);
  };

  // Negative dim: i64 dims are attacker-controlled.
  {
    std::vector<uint8_t> b{wire::kTagArray, 4 /* f32 */, 1 /* ndim */};
    put_i64(&b, -8);
    CHECK_THROWS(decode_bytes(b), wire::WireError);
  }
  // Two dims whose product wraps size_t back to something tiny.
  {
    std::vector<uint8_t> b{wire::kTagArray, 4, 2};
    put_i64(&b, int64_t{1} << 62);
    put_i64(&b, int64_t{1} << 62);
    b.push_back(0);  // a little "payload" so a wrapped size could "fit"
    CHECK_THROWS(decode_bytes(b), wire::WireError);
  }
  // Single dim so large that numel*itemsize overflows.
  {
    std::vector<uint8_t> b{wire::kTagArray, 5 /* f64 */, 1};
    put_i64(&b, int64_t{1} << 61);
    CHECK_THROWS(decode_bytes(b), wire::WireError);
  }
  // Unknown dtype byte.
  {
    std::vector<uint8_t> b{wire::kTagArray, 0x7f, 0};
    CHECK_THROWS(decode_bytes(b), std::invalid_argument);
  }
  // Huge string length must not wrap the bounds check.
  {
    std::vector<uint8_t> b{wire::kTagString, 0xff, 0xff, 0xff, 0xff};
    CHECK_THROWS(decode_bytes(b), wire::WireError);
  }
  // Huge list/dict counts must be rejected before any allocation.
  {
    std::vector<uint8_t> b{wire::kTagList, 0xff, 0xff, 0xff, 0xff};
    CHECK_THROWS(decode_bytes(b), wire::WireError);
  }
  {
    std::vector<uint8_t> b{wire::kTagDict, 0xff, 0xff, 0xff, 0xff};
    CHECK_THROWS(decode_bytes(b), wire::WireError);
  }
  // Zero-sized dims stay legal: shape (0, 5) decodes to an empty array,
  // and a LATER zero dim must not demand bytes for the earlier dims.
  {
    std::vector<uint8_t> b{wire::kTagArray, 4, 2};
    put_i64(&b, 0);
    put_i64(&b, 5);
    wire::ValueNest out = decode_bytes(b);
    CHECK(out.leaf().array.shape() == (std::vector<int64_t>{0, 5}));
  }
  {
    std::vector<uint8_t> b{wire::kTagArray, 4, 2};
    put_i64(&b, 5);
    put_i64(&b, 0);
    wire::ValueNest out = decode_bytes(b);
    CHECK(out.leaf().array.shape() == (std::vector<int64_t>{5, 0}));
  }
  std::printf("wire malformed-frame rejection ok\n");
}

static void test_batching_queue() {
  CHECK_THROWS(BatchingQueue<int>(0, 0, 1, {}, {}, true),
               std::invalid_argument);
  CHECK_THROWS(BatchingQueue<int>(0, 4, 2, {}, {}, true),
               std::invalid_argument);

  BatchingQueue<int> queue(0, 3, 8, {}, {}, true);
  for (int i = 0; i < 3; ++i) {
    queue.enqueue(ArrayNest(make_array(DType::kI64, {1, 2}, i)), i);
  }
  auto [batch, payloads] = queue.dequeue_many();
  CHECK(batch.front().shape() == (std::vector<int64_t>{3, 2}));
  CHECK(payloads == (std::vector<int>{0, 1, 2}));

  queue.close();
  CHECK_THROWS(queue.enqueue(ArrayNest(make_array(DType::kI64, {1}, 0)), 0),
               ClosedBatchingQueue);
  CHECK_THROWS(queue.dequeue_many(), QueueStopped);
  CHECK_THROWS(queue.close(), std::runtime_error);
  std::printf("batching queue ok\n");
}

// timeout_ms=0: an immediate timeout returns whatever rows exist, and an
// EMPTY queue must block idle for the first item instead of busy-spinning
// wait_for(0) in a loop (regression: pegged a core until an enqueue).
static void test_batching_queue_timeout_zero() {
  {
    BatchingQueue<int> queue(0, 4, 8, int64_t{0}, {}, true);
    queue.enqueue(ArrayNest(make_array(DType::kI64, {1, 2}, 7)), 7);
    auto [batch, payloads] = queue.dequeue_many();  // partial, no wait
    CHECK(payloads == (std::vector<int>{7}));
  }
  {
    BatchingQueue<int> queue(0, 4, 8, int64_t{0}, {}, true);
    timespec cpu0{}, cpu1{};
    std::thread consumer([&] {
      clock_gettime(CLOCK_THREAD_CPUTIME_ID, &cpu0);
      auto [batch, payloads] = queue.dequeue_many();
      clock_gettime(CLOCK_THREAD_CPUTIME_ID, &cpu1);
      CHECK(payloads == (std::vector<int>{1}));
    });
    std::this_thread::sleep_for(std::chrono::milliseconds(200));
    queue.enqueue(ArrayNest(make_array(DType::kI64, {1, 2}, 1)), 1);
    consumer.join();
    double cpu_ms = (cpu1.tv_sec - cpu0.tv_sec) * 1e3 +
                    (cpu1.tv_nsec - cpu0.tv_nsec) / 1e6;
    // 200ms wall blocked on an empty queue must cost ~0 CPU; a busy-spin
    // burns the full 200ms.
    CHECK(cpu_ms < 100.0);
  }
  std::printf("batching queue timeout-zero ok\n");
}

static void test_queue_stress() {
  BatchingQueue<int64_t> queue(0, 1, 16, {}, {}, true);
  constexpr int kProducers = 8, kItems = 200;
  std::vector<std::thread> producers;
  std::atomic<int64_t> total{0};
  std::set<int64_t> seen;
  std::mutex seen_mu;

  std::vector<std::thread> consumers;
  for (int c = 0; c < 4; ++c) {
    consumers.emplace_back([&] {
      while (true) {
        try {
          auto [batch, payloads] = queue.dequeue_many();
          std::lock_guard<std::mutex> lock(seen_mu);
          for (int64_t p : payloads) seen.insert(p);
          total += static_cast<int64_t>(payloads.size());
        } catch (const QueueStopped&) {
          return;
        }
      }
    });
  }
  for (int p = 0; p < kProducers; ++p) {
    producers.emplace_back([&queue, p] {
      for (int i = 0; i < kItems; ++i) {
        queue.enqueue(ArrayNest(make_array(DType::kI64, {1}, i)),
                      static_cast<int64_t>(p) * kItems + i);
      }
    });
  }
  for (auto& t : producers) t.join();
  while (queue.size() > 0)
    std::this_thread::sleep_for(std::chrono::milliseconds(1));
  queue.close();
  for (auto& t : consumers) t.join();
  CHECK(total == kProducers * kItems);
  CHECK(seen.size() == kProducers * kItems);
  std::printf("queue stress ok (%lld items)\n",
              static_cast<long long>(total.load()));
}

static void test_dynamic_batcher() {
  DynamicBatcher batcher(/*batch_dim=*/0, 1, 64, /*timeout_ms=*/20);

  std::thread producer([&batcher] {
    ArrayNest out = batcher.compute(ArrayNest(make_array(DType::kI64, {1, 2}, 3)));
    const Array& a = out.front();
    CHECK(a.shape() == (std::vector<int64_t>{1, 2}));
    CHECK(reinterpret_cast<const int64_t*>(a.data())[0] == 6);
  });

  auto batch = batcher.get_batch();
  CHECK(batch->size() == 1);
  ArrayNest outputs = batch->inputs().map([](const Array& a) {
    Array out = a.clone();
    int64_t* p = reinterpret_cast<int64_t*>(out.mutable_data());
    for (int64_t i = 0; i < out.numel(); ++i) p[i] *= 2;
    return out;
  });
  batch->set_outputs(outputs);
  CHECK_THROWS(batch->set_outputs(outputs), std::runtime_error);
  producer.join();

  // Dropped batch breaks the promise.
  std::thread victim([&batcher] {
    CHECK_THROWS(
        batcher.compute(ArrayNest(make_array(DType::kI64, {1, 1}, 0))),
        AsyncError);
  });
  batcher.get_batch().reset();  // drop without outputs
  victim.join();

  // close() wakes pending compute callers.
  std::thread pending([&batcher] {
    CHECK_THROWS(
        batcher.compute(ArrayNest(make_array(DType::kI64, {1, 1}, 0))),
        AsyncError);
  });
  std::this_thread::sleep_for(std::chrono::milliseconds(50));
  batcher.close();
  pending.join();
  std::printf("dynamic batcher ok\n");
}


// Raw-item FIFO intake (the BatchArena path: --superstep_k native).
static void test_batching_queue_dequeue_item() {
  BatchingQueue<int> queue(1, 1, 8, {}, {}, true);
  for (int i = 0; i < 3; ++i) {
    queue.enqueue(ArrayNest(make_array(DType::kI64, {2, 1}, i)), i);
  }
  for (int i = 0; i < 3; ++i) {
    auto [inputs, rows] = queue.dequeue_item();
    CHECK(rows == 1);  // rows along batch_dim=1
    CHECK(reinterpret_cast<const int64_t*>(inputs.front().data())[0] == i);
  }
  queue.close();
  CHECK_THROWS(queue.dequeue_item(), QueueStopped);
  std::printf("batching queue dequeue_item ok\n");
}

// Batcher stage stamps: request_wait/rtt/batch_size accumulate and
// snapshot(reset) starts a fresh interval.
static void test_batcher_telemetry() {
  DynamicBatcher batcher(0, 1, 64, 20);
  std::thread producer([&batcher] {
    batcher.compute(ArrayNest(make_array(DType::kI64, {1, 2}, 3)));
  });
  auto batch = batcher.get_batch();
  batch->set_outputs(batch->inputs());
  producer.join();
  auto telemetry = batcher.telemetry();
  CHECK(telemetry->batches.load() == 1);
  CHECK(telemetry->rows.load() == 1);
  HistSnapshot wait = telemetry->request_wait_s.snapshot(true);
  CHECK(wait.count == 1);
  CHECK(wait.total >= 0.0);
  CHECK(telemetry->request_wait_s.snapshot(false).count == 0);  // reset
  HistSnapshot rtt = telemetry->request_rtt_s.snapshot(false);
  CHECK(rtt.count == 1);
  CHECK(rtt.total >= wait.total);
  // Bucket geometry matches telemetry/metrics.py: 1e-3 lands in bucket
  // 1 + floor(log(1e-3/1e-9)/log(2^0.25)) = 80.
  CHECK(telemetry_bucket_index(1e-3) == 80);
  CHECK(telemetry_bucket_index(0.0) == 0);
  batcher.close();
  std::printf("batcher telemetry ok\n");
}

// compute() hands its caller the instant set_outputs was ENTERED
// (ISSUE 66): where request_rtt_s ends and the caller's account of the
// reply starts. Through a router it is the serving batcher's.
static void test_batcher_reply_instant() {
  auto batcher = std::make_shared<DynamicBatcher>(0, 1, 64, 20);
  SliceRouter router({batcher});
  for (InferenceClient* client :
       {static_cast<InferenceClient*>(batcher.get()),
        static_cast<InferenceClient*>(&router)}) {
    int64_t replied_ns = 0, returned_ns = 0;
    const int64_t before_ns = monotonic_ns();
    std::thread producer([&] {
      client->compute(ArrayNest(make_array(DType::kI64, {1, 2}, 3)), 600,
                      &replied_ns);
      returned_ns = monotonic_ns();
    });
    auto batch = batcher->get_batch();
    const int64_t entering_ns = monotonic_ns();
    batch->set_outputs(batch->inputs());
    const int64_t left_ns = monotonic_ns();
    producer.join();
    CHECK(before_ns <= entering_ns);
    CHECK(entering_ns <= replied_ns);
    CHECK(replied_ns <= left_ns);
    CHECK(replied_ns <= returned_ns);
  }
  // A caller that asks for none gets none (the Python binding's path).
  std::thread producer([&batcher] {
    batcher->compute(ArrayNest(make_array(DType::kI64, {1, 2}, 3)));
  });
  auto batch = batcher->get_batch();
  batch->set_outputs(batch->inputs());
  producer.join();
  batcher->close();
  std::printf("batcher reply instant ok\n");
}

// splitmix64 slice hash (ISSUE 16): the well-known finalizer vector for
// input 0 pins the constants; slot routing must be deterministic, in
// range, and wrap negative ids exactly like the Python `& (2**64-1)`.
static void test_routing_hash() {
  // splitmix64 state 0 -> first output (the published reference vector;
  // tests/test_native_routing.py checks the same value against
  // placement._mix64 for the cross-language bit-identity pin).
  CHECK(splitmix64(0) == 0xE220A8397B1DCDAFULL);
  for (int64_t slot = 0; slot < 1000; ++slot) {
    int64_t s = slice_for_slot(slot, 3);
    CHECK(s >= 0 && s < 3);
    CHECK(s == slice_for_slot(slot, 3));  // stable
  }
  // Negative ids wrap through uint64, not UB.
  CHECK(slice_for_slot(-1, 5) ==
        static_cast<int64_t>(splitmix64(~uint64_t{0}) % 5));
  CHECK_THROWS(slice_for_slot(0, 0), std::invalid_argument);
  // All slices earn traffic over a modest slot range (the hash is a
  // finalizer, not a permutation — but 256 slots over 4 slices missing
  // one entirely would mean a broken constant).
  std::set<int64_t> hit;
  for (int64_t slot = 0; slot < 256; ++slot) hit.insert(slice_for_slot(slot, 4));
  CHECK(hit.size() == 4);
  std::printf("routing hash ok\n");
}

// SliceRouter: slot-framed requests land on the hash-assigned slice's
// batcher (same reply identity the Python router guarantees); slot-less
// requests round-robin; counters and close semantics match.
static void test_slice_router() {
  auto b0 = std::make_shared<DynamicBatcher>(0, 1, 64, 20);
  auto b1 = std::make_shared<DynamicBatcher>(0, 1, 64, 20);
  SliceRouter router({b0, b1});
  CHECK(router.n_slices() == 2);

  // Each slice's consumer echoes inputs with the slice index added, so
  // a reply proves which batcher served it.
  std::atomic<bool> stop{false};
  auto consumer = [&stop](std::shared_ptr<DynamicBatcher> b, int64_t tag) {
    while (true) {
      try {
        auto batch = b->get_batch();
        ArrayNest out = batch->inputs().dict().at("env").map(
            [tag](const Array& a) {
              Array o = a.clone();
              int64_t* p = reinterpret_cast<int64_t*>(o.mutable_data());
              for (int64_t i = 0; i < o.numel(); ++i) p[i] += tag;
              return o;
            });
        ArrayNest::Dict reply;
        reply.emplace("outputs", std::move(out));
        batch->set_outputs(ArrayNest(std::move(reply)));
      } catch (const QueueStopped&) {
        return;
      }
    }
    (void)stop;
  };
  std::thread c0(consumer, b0, 1000);
  std::thread c1(consumer, b1, 2000);

  constexpr int kSlots = 16;
  std::vector<std::thread> producers;
  for (int slot = 0; slot < kSlots; ++slot) {
    producers.emplace_back([&router, slot] {
      ArrayNest::Dict inputs;
      inputs.emplace("env",
                     ArrayNest(make_array(DType::kI64, {1, 1}, slot)));
      Array slot_arr(DType::kI32, {1, 1});
      *reinterpret_cast<int32_t*>(slot_arr.mutable_data()) =
          static_cast<int32_t>(slot);
      inputs.emplace("slot", ArrayNest(std::move(slot_arr)));
      ArrayNest out = router.compute(ArrayNest(std::move(inputs)));
      int64_t value = *reinterpret_cast<const int64_t*>(
          out.dict().at("outputs").front().data());
      int64_t expect_tag = slice_for_slot(slot, 2) == 0 ? 1000 : 2000;
      CHECK(value == slot + expect_tag);
    });
  }
  for (auto& t : producers) t.join();

  std::vector<int64_t> counts = router.request_counts();
  CHECK(counts.size() == 2);
  CHECK(counts[0] + counts[1] == kSlots);
  int64_t expect0 = 0;
  for (int slot = 0; slot < kSlots; ++slot)
    if (slice_for_slot(slot, 2) == 0) ++expect0;
  CHECK(counts[0] == expect0);

  // Slot-less requests round-robin across both slices.
  std::vector<std::thread> rr;
  for (int i = 0; i < 4; ++i) {
    rr.emplace_back([&router] {
      ArrayNest::Dict inputs;
      inputs.emplace("env", ArrayNest(make_array(DType::kI64, {1, 1}, 7)));
      router.compute(ArrayNest(std::move(inputs)));
    });
  }
  for (auto& t : rr) t.join();
  counts = router.request_counts();
  CHECK(counts[0] + counts[1] == kSlots + 4);

  CHECK(router.size() == 0);
  CHECK(!router.is_closed());
  router.close();
  CHECK(router.is_closed());
  router.close();  // second close swallows (driver closers also close slices)
  c0.join();
  c1.join();
  std::printf("slice router ok\n");
}

// ReplicaRouter: serving flag routes replica-first, falls back to
// central on replica failure/closure, propagates sheds, and counts each
// request in exactly one series.
static void test_replica_router() {
  auto central = std::make_shared<DynamicBatcher>(0, 1, 64, 20);
  auto replica = std::make_shared<DynamicBatcher>(0, 1, 64, 20);
  auto router = std::make_shared<ReplicaRouter>(central, replica);

  auto serve_one = [](std::shared_ptr<DynamicBatcher> b, int64_t tag) {
    auto batch = b->get_batch();
    ArrayNest out = batch->inputs().map([tag](const Array& a) {
      Array o = a.clone();
      int64_t* p = reinterpret_cast<int64_t*>(o.mutable_data());
      for (int64_t i = 0; i < o.numel(); ++i) p[i] += tag;
      return o;
    });
    batch->set_outputs(out);
  };

  // Degraded (flag down, the boot state): requests go central.
  CHECK(!router->serving());
  std::thread p1([&router] {
    ArrayNest out =
        router->compute(ArrayNest(make_array(DType::kI64, {1, 1}, 1)));
    CHECK(*reinterpret_cast<const int64_t*>(out.front().data()) == 101);
  });
  serve_one(central, 100);
  p1.join();
  CHECK(router->central_requests() == 1);
  CHECK(router->replica_requests() == 0);

  // Healthy: requests go replica.
  router->set_serving(true);
  std::thread p2([&router] {
    ArrayNest out =
        router->compute(ArrayNest(make_array(DType::kI64, {1, 1}, 2)));
    CHECK(*reinterpret_cast<const int64_t*>(out.front().data()) == 202);
  });
  serve_one(replica, 200);
  p2.join();
  CHECK(router->replica_requests() == 1);

  // Replica-side serving failure (dropped batch -> AsyncError): the
  // request falls back to central and lands in ONE series.
  std::thread p3([&router] {
    ArrayNest out =
        router->compute(ArrayNest(make_array(DType::kI64, {1, 1}, 3)));
    CHECK(*reinterpret_cast<const int64_t*>(out.front().data()) == 103);
  });
  replica->get_batch().reset();  // drop without outputs -> AsyncError
  serve_one(central, 100);
  p3.join();
  CHECK(router->replica_requests() == 1);
  CHECK(router->central_requests() == 2);

  // A closed replica with the flag still up also falls back.
  replica->close();
  std::thread p4([&router] {
    ArrayNest out =
        router->compute(ArrayNest(make_array(DType::kI64, {1, 1}, 4)));
    CHECK(*reinterpret_cast<const int64_t*>(out.front().data()) == 104);
  });
  serve_one(central, 100);
  p4.join();
  CHECK(router->central_requests() == 3);

  CHECK(!router->is_closed());  // central still open
  router->close();  // replica already closed: swallowed
  CHECK(router->is_closed());
  std::printf("replica router ok\n");
}

// Replica sheds propagate to the caller (the actor's retry contract)
// instead of silently falling back — central fallback on a shed would
// defeat the admission gate exactly when it matters.
static void test_replica_router_shed() {
  auto central = std::make_shared<DynamicBatcher>(0, 1, 64, 20);
  auto replica = std::make_shared<DynamicBatcher>(
      0, 1, 64, 20, /*shed_max_queue_depth=*/1);
  ReplicaRouter router(central, replica);
  router.set_serving(true);
  // Fill the replica queue to its bound, then the next compute sheds.
  std::thread filler([&replica] {
    CHECK_THROWS(
        replica->compute(ArrayNest(make_array(DType::kI64, {1, 1}, 0)), 1),
        std::runtime_error);  // compute timeout — nobody serves it
  });
  while (replica->size() < 1)
    std::this_thread::sleep_for(std::chrono::milliseconds(1));
  CHECK_THROWS(
      router.compute(ArrayNest(make_array(DType::kI64, {1, 1}, 1))),
      ShedError);
  CHECK(router.central_requests() == 0);
  filler.join();
  replica->close();
  central->close();
  std::printf("replica router shed ok\n");
}

// try_dequeue_upto: non-blocking, row-budgeted, FIFO.
static void test_try_dequeue_upto() {
  BatchingQueue<int> queue(0, 1, 8, {}, {}, true);
  for (int i = 0; i < 3; ++i)
    queue.enqueue(ArrayNest(make_array(DType::kI64, {1, 1}, i)), i);
  auto two = queue.try_dequeue_upto(2);
  CHECK(two.size() == 2);
  CHECK(two[0].payload == 0 && two[1].payload == 1);
  auto rest = queue.try_dequeue_upto(10);
  CHECK(rest.size() == 1 && rest[0].payload == 2);
  CHECK(queue.try_dequeue_upto(5).empty());  // empty: returns, never waits
  queue.close();
  std::printf("try_dequeue_upto ok\n");
}

// Continuous batching (ISSUE 16): under producer pressure every request
// is served or shed/expired EXACTLY — resubmitted == shed + expired —
// and the top-up path (rolled) keeps admitted requests flowing.
static void test_continuous_batcher() {
  DynamicBatcher batcher(0, 1, 4, /*timeout_ms=*/5,
                         /*shed_max_queue_depth=*/4,
                         /*deadline_ms=*/50.0,
                         /*slo_target_ms=*/std::nullopt,
                         /*continuous=*/true);
  constexpr int kProducers = 4, kRequests = 50;
  std::atomic<int64_t> served{0}, resubmitted{0};
  std::vector<std::thread> producers;
  for (int p = 0; p < kProducers; ++p) {
    producers.emplace_back([&batcher, &served, &resubmitted] {
      for (int i = 0; i < kRequests; ++i) {
        try {
          batcher.compute(ArrayNest(make_array(DType::kI64, {1, 1}, i)));
          served.fetch_add(1);
        } catch (const ShedError&) {
          // No retry here: the test counts one shed reply per request
          // so the audit below is exact without retry bookkeeping.
          resubmitted.fetch_add(1);
        }
      }
    });
  }
  std::thread consumer([&batcher] {
    while (true) {
      try {
        auto batch = batcher.get_batch();
        // A slow-ish consumer: lets the queue build so the deadline
        // gate and the top-up both see real traffic.
        std::this_thread::sleep_for(std::chrono::milliseconds(2));
        batch->set_outputs(batch->inputs());
      } catch (const QueueStopped&) {
        return;
      }
    }
  });
  for (auto& t : producers) t.join();
  while (batcher.size() > 0)
    std::this_thread::sleep_for(std::chrono::milliseconds(1));
  batcher.close();
  consumer.join();
  auto telemetry = batcher.telemetry();
  int64_t shed = telemetry->shed.load();
  int64_t expired = telemetry->expired.load();
  // The exactness invariant the chaos harness audits, on the
  // continuous path: every rejected request is accounted once.
  CHECK(resubmitted.load() == shed + expired);
  CHECK(served.load() + resubmitted.load() == kProducers * kRequests);
  CHECK(telemetry->admitted.load() == served.load() + expired);
  CHECK(telemetry->rolled.load() >= 0);
  std::printf(
      "continuous batcher ok (served=%lld shed=%lld expired=%lld "
      "rolled=%lld)\n",
      static_cast<long long>(served.load()), static_cast<long long>(shed),
      static_cast<long long>(expired),
      static_cast<long long>(telemetry->rolled.load()));
}

// SPSC ring: frame roundtrip, wrap at the segment end, inline marker,
// ring-eligibility cap.
static void test_shm_ring_roundtrip() {
  shm::ShmRing ring = shm::ShmRing::create(256);
  CHECK(ring.capacity() == 256);
  CHECK(ring.max_frame_bytes() == 256 / 2 - 4);

  // Attach sees the same bytes.
  shm::ShmRing peer = shm::ShmRing::attach(ring.name());
  CHECK(peer.capacity() == 256);

  auto write = [&](const std::vector<uint8_t>& payload) {
    ring.write_frame(payload.data(), payload.size(), nullptr);
  };
  auto read_check = [&](const std::vector<uint8_t>& expected) {
    CHECK(peer.has_frame());
    shm::ShmRing::Frame f = peer.read_frame();
    CHECK(!f.is_inline);
    CHECK(f.size == expected.size());
    CHECK(std::memcmp(f.data, expected.data(), f.size) == 0);
    peer.release(f.advance);
  };

  // Enough frames to wrap several times.
  for (int round = 0; round < 40; ++round) {
    std::vector<uint8_t> payload(37 + (round % 50));
    for (size_t i = 0; i < payload.size(); ++i)
      payload[i] = static_cast<uint8_t>(round + i);
    write(payload);
    read_check(payload);
  }

  // Inline marker holds the order slot.
  std::vector<uint8_t> small{1, 2, 3};
  write(small);
  ring.write_inline_marker(nullptr);
  write(small);
  read_check(small);
  shm::ShmRing::Frame f = peer.read_frame();
  CHECK(f.is_inline);
  peer.release(f.advance);
  read_check(small);
  CHECK(!peer.has_frame());

  // Over-capacity frames are rejected outright.
  std::vector<uint8_t> huge(300);
  CHECK_THROWS(ring.write_frame(huge.data(), huge.size(), nullptr),
               wire::WireError);
  peer.close();
  ring.close();
  std::printf("shm ring roundtrip ok\n");
}

// Adaptive recheck policy (ISSUE 12): a recheck-heavy window tightens
// the bound toward the floor, quiet windows relax it to the cap, and a
// mixed window inside the hysteresis band holds it.
static void test_shm_ring_adaptive_recheck() {
  shm::AdaptiveRecheck policy;
  CHECK(policy.bound_ms() == shm::kWakeRecheckMs);
  for (int i = 0; i < shm::kRecheckWindow; ++i) policy.record(true);
  CHECK(policy.bound_ms() == shm::kWakeRecheckMs / 2);
  for (int i = 0; i < 8 * shm::kRecheckWindow; ++i) policy.record(true);
  CHECK(policy.bound_ms() == shm::kRecheckMinMs);
  for (int i = 0; i < 12 * shm::kRecheckWindow; ++i) policy.record(false);
  CHECK(policy.bound_ms() == shm::kRecheckMaxMs);
  shm::AdaptiveRecheck held;
  for (int i = 0; i < shm::kRecheckWindow; ++i)
    held.record(i < shm::kRecheckTighten - 1);
  CHECK(held.bound_ms() == shm::kWakeRecheckMs);
  std::printf("shm ring adaptive recheck ok\n");
}

// Chaos ring-poke hook (ISSUE 12): header corruption observably lands
// in the queued frame (tail-stability contract) and the reader's next
// read_frame deterministically rejects it; an empty ring reports retry.
static void test_shm_ring_corrupt() {
  shm::ShmRing ring = shm::ShmRing::create(256);
  shm::ShmRing peer = shm::ShmRing::attach(ring.name());
  CHECK(peer.corrupt_tail_frame(/*header=*/true) == 0);  // empty: retry
  std::vector<uint8_t> payload(24, 0x42);
  ring.write_frame(payload.data(), payload.size(), nullptr);
  CHECK(peer.corrupt_tail_frame(/*header=*/true) == 1);
  CHECK_THROWS(peer.read_frame(), wire::WireError);
  peer.close();
  ring.close();
  std::printf("shm ring corrupt ok\n");
}

static wire::ValueNest step_like_message(int64_t tag, int64_t frame_cells) {
  wire::ValueNest::Dict d;
  d.emplace("type", wire::ValueNest(wire::Value::of_string("step")));
  d.emplace("frame", wire::ValueNest(wire::Value::of(
                         make_array(DType::kU8, {frame_cells}, tag & 0xff))));
  d.emplace("reward", wire::ValueNest(wire::Value::of(
                          make_array(DType::kF32, {}, tag))));
  d.emplace("count", wire::ValueNest(wire::Value::of_int(tag)));
  return wire::ValueNest(std::move(d));
}

// Full transport pair over a socketpair doorbell: ordering and contents
// across ring frames AND oversized inline frames, both directions.
static void test_shm_ring_transport() {
  int fds[2];
  CHECK(::socketpair(AF_UNIX, SOCK_STREAM, 0, fds) == 0);
  // Small rings force wraps and route big frames inline.
  shm::ShmRing s2c = shm::ShmRing::create(4096);
  shm::ShmRing c2s = shm::ShmRing::create(1024);
  shm::ShmRing s2c_peer = shm::ShmRing::attach(s2c.name());
  shm::ShmRing c2s_peer = shm::ShmRing::attach(c2s.name());
  shm::ShmTransport server(fds[0], std::move(s2c), std::move(c2s));
  shm::ShmTransport client(fds[1], std::move(c2s_peer), std::move(s2c_peer));

  constexpr int kMessages = 200;
  std::thread server_thread([&server] {
    for (int i = 0; i < kMessages; ++i) {
      // Every 7th frame is bigger than the obs ring allows -> inline.
      int64_t cells = (i % 7 == 6) ? 8192 : 64 + i;
      server.send(step_like_message(i, cells));
      wire::ValueNest action = server.recv();
      CHECK(action.dict().at("action").leaf().i == i);
    }
  });
  for (int i = 0; i < kMessages; ++i) {
    wire::ValueNest step = client.recv();
    const auto& dict = step.dict();
    CHECK(dict.at("count").leaf().i == i);
    int64_t cells = (i % 7 == 6) ? 8192 : 64 + i;
    const Array& frame = dict.at("frame").leaf().array;
    CHECK(frame.numel() == cells);
    CHECK(frame.data()[0] == (i & 0xff));
    wire::ValueNest::Dict a;
    a.emplace("type", wire::ValueNest(wire::Value::of_string("action")));
    a.emplace("action", wire::ValueNest(wire::Value::of_int(i)));
    client.send(wire::ValueNest(std::move(a)));
  }
  server_thread.join();
  // EOF surfaces as SocketError once the peer closes.
  server.close();
  CHECK_THROWS(client.recv(), SocketError);
  client.close();
  std::printf("shm ring transport ok (%d messages)\n", kMessages);
}

// Threaded stress at a rate-matched cadence: the coalesced-doorbell
// waiting-flag handshake must neither deadlock nor reorder. (TSan lane:
// build_native.sh --sanitize=thread --filter=ring.)
static void test_shm_ring_stress() {
  int fds[2];
  CHECK(::socketpair(AF_UNIX, SOCK_STREAM, 0, fds) == 0);
  shm::ShmRing a = shm::ShmRing::create(2048);
  shm::ShmRing b = shm::ShmRing::create(2048);
  shm::ShmRing a_peer = shm::ShmRing::attach(a.name());
  shm::ShmRing b_peer = shm::ShmRing::attach(b.name());
  shm::ShmTransport left(fds[0], std::move(a), std::move(b));
  shm::ShmTransport right(fds[1], std::move(b_peer), std::move(a_peer));

  constexpr int kMessages = 2000;
  std::thread producer([&left] {
    for (int i = 0; i < kMessages; ++i) {
      left.send(step_like_message(i, 16 + (i % 113)));
    }
  });
  for (int i = 0; i < kMessages; ++i) {
    wire::ValueNest step = right.recv();
    CHECK(step.dict().at("count").leaf().i == i);
  }
  producer.join();
  left.close();
  right.close();
  std::printf("shm ring stress ok (%d messages)\n", kMessages);
}

void test_env_server() {
  // Counting "env" implemented as hooks: initial -> step 0; each action
  // increments by the action value. A throwing step produces an error
  // frame. stop() severs live streams mid-recv.
  std::string address = "unix:/tmp/tbt_test_env_server";
  auto factory = [] {
    auto count = std::make_shared<int64_t>(0);
    StreamHooks hooks;
    hooks.initial = [count] {
      wire::ValueNest::Dict d;
      d.emplace("type", wire::ValueNest(wire::Value::of_string("step")));
      d.emplace("count", wire::ValueNest(wire::Value::of_int(*count)));
      return wire::ValueNest(std::move(d));
    };
    hooks.step = [count](const wire::ValueNest& msg) {
      const auto& dict = msg.dict();
      int64_t action = dict.at("action").leaf().i;
      if (action < 0) throw std::runtime_error("negative action");
      *count += action;
      wire::ValueNest::Dict d;
      d.emplace("type", wire::ValueNest(wire::Value::of_string("step")));
      d.emplace("count", wire::ValueNest(wire::Value::of_int(*count)));
      return wire::ValueNest(std::move(d));
    };
    hooks.close = [] {};
    return hooks;
  };
  EnvServer server(address, factory);
  std::thread server_thread([&server] { server.run(); });

  auto send_action = [](FramedSocket& sock, int64_t a) {
    wire::ValueNest::Dict d;
    d.emplace("type", wire::ValueNest(wire::Value::of_string("action")));
    d.emplace("action", wire::ValueNest(wire::Value::of_int(a)));
    sock.send(wire::ValueNest(std::move(d)));
  };

  {
    FramedSocket sock;
    sock.connect(address, 10.0);
    wire::ValueNest initial = sock.recv();
    CHECK(initial.dict().at("count").leaf().i == 0);
    send_action(sock, 5);
    CHECK(sock.recv().dict().at("count").leaf().i == 5);
    send_action(sock, 2);
    CHECK(sock.recv().dict().at("count").leaf().i == 7);
  }
  {
    // Fresh stream gets a fresh env (count resets).
    FramedSocket sock;
    sock.connect(address, 10.0);
    CHECK(sock.recv().dict().at("count").leaf().i == 0);
    // Error path: hook throws -> error frame.
    send_action(sock, -1);
    wire::ValueNest err = sock.recv();
    CHECK(err.dict().at("type").leaf().s == "error");
    CHECK(err.dict().at("message").leaf().s.find("negative action") !=
          std::string::npos);
  }
  {
    // stop() severs a live stream blocked in recv.
    FramedSocket sock;
    sock.connect(address, 10.0);
    CHECK(sock.recv().dict().at("count").leaf().i == 0);
    std::thread stopper([&server] {
      std::this_thread::sleep_for(std::chrono::milliseconds(50));
      server.stop();
    });
    CHECK_THROWS(sock.recv(), SocketError);
    stopper.join();
  }
  server_thread.join();
  server.join_all();
  std::printf("env server ok\n");
}

// A step message as an env server's hooks make it: the env's six keys.
static wire::ValueNest env_step_message(int64_t t) {
  wire::ValueNest::Dict d;
  d.emplace("type", wire::ValueNest(wire::Value::of_string("step")));
  auto put = [&d](const char* key, Array a) {
    d.emplace(key, wire::ValueNest(wire::Value::of(std::move(a))));
  };
  put("frame", make_array(DType::kU8, {4, 4, 1}, t % 255));
  put("reward", make_array(DType::kF32, {}, 0));
  put("done", make_array(DType::kBool, {}, 0));
  put("episode_step", make_array(DType::kI32, {}, 0));
  put("episode_return", make_array(DType::kF32, {}, 0));
  put("last_action", make_array(DType::kI32, {}, 0));
  return wire::ValueNest(std::move(d));
}

// An InferenceClient that holds its caller back after the reply: what a
// late wake or a busy core does to an actor thread.
class HeldBackClient : public InferenceClient {
 public:
  HeldBackClient(std::shared_ptr<InferenceClient> inner, int hold_ms)
      : inner_(std::move(inner)), hold_ms_(hold_ms) {}
  ArrayNest compute(ArrayNest inputs, int64_t timeout_s = 600,
                    int64_t* replied_ns = nullptr) override {
    ArrayNest out = inner_->compute(std::move(inputs), timeout_s, replied_ns);
    std::this_thread::sleep_for(std::chrono::milliseconds(hold_ms_));
    return out;
  }
  int64_t size() const override { return inner_->size(); }
  bool is_closed() const override { return inner_->is_closed(); }
  void close() override { inner_->close(); }

 private:
  std::shared_ptr<InferenceClient> inner_;
  const int hold_ms_;
};

// One actor cycle (ISSUE 66): eight actor loops against the C++ env
// server whose step takes 3 ms, their threads held back 2 ms after each
// reply. The seven stage histograms cover the same iterations; the
// env's three terms sum to env_rtt_s; reply_wake_s sees the hold and
// request_rtt_s does not; the terms add up to the cycle; a snapshot
// starts a fresh interval.
void test_actor_cycle() {
  static constexpr int kActors = 8, kRounds = 5, kStepMs = 3, kHoldMs = 2;
  std::string address = "unix:/tmp/tbt_test_actor_cycle";
  auto factory = [] {
    auto t = std::make_shared<int64_t>(0);
    StreamHooks hooks;
    hooks.initial = [t] { return env_step_message(*t); };
    hooks.step = [t](const wire::ValueNest&) {
      std::this_thread::sleep_for(std::chrono::milliseconds(kStepMs));
      return env_step_message(++*t);
    };
    hooks.close = [] {};
    return hooks;
  };
  EnvServer server(address, factory);
  std::thread server_thread([&server] { server.run(); });

  auto batcher = std::make_shared<DynamicBatcher>(1, kActors, kActors,
                                                  std::nullopt);
  auto learner_queue = std::make_shared<ActorPool::LearnerQueue>(
      1, 1, 1, std::nullopt, std::nullopt, false);
  ActorPool pool(/*unroll_length=*/1000, learner_queue,
                 std::make_shared<HeldBackClient>(batcher, kHoldMs),
                 std::vector<std::string>(kActors, address),
                 ArrayNest(make_array(DType::kI64, {1, 1}, 0)),
                 /*connect_timeout_s=*/10);
  std::thread pool_thread([&pool] { pool.run(); });

  auto answer = [&batcher] {
    auto batch = batcher->get_batch();  // blocks for all eight rows
    CHECK(batch->size() == kActors);
    ArrayNest::Dict outputs;
    outputs.emplace("action",
                    ArrayNest(make_array(DType::kI32, {1, kActors}, 0)));
    ArrayNest::Dict reply;
    reply.emplace("outputs", ArrayNest(std::move(outputs)));
    reply.emplace("agent_state",
                  ArrayNest(make_array(DType::kI64, {1, kActors}, 0)));
    batch->set_outputs(ArrayNest(std::move(reply)));
  };
  auto all_waiting = [&batcher] {
    while (batcher->size() < kActors)
      std::this_thread::sleep_for(std::chrono::milliseconds(1));
  };
  answer();  // the priming requests: in no cycle
  all_waiting();
  batcher->telemetry()->request_rtt_s.snapshot(true);
  for (const auto& [name, h] : pool.stage_snapshots()) CHECK(h.count == 0);
  for (int i = 0; i < kRounds; ++i) {
    answer();
    all_waiting();  // every actor has finished cycle i
  }

  std::map<std::string, HistSnapshot> stages;
  for (auto& [name, h] : pool.stage_snapshots()) stages.emplace(name, h);
  CHECK(stages.size() == 7);
  for (const auto& [name, h] : stages) CHECK(h.count == kActors * kRounds);
  HistSnapshot rtt = batcher->telemetry()->request_rtt_s.snapshot(true);
  CHECK(rtt.count == kActors * kRounds);
  // snapshot(reset) started a fresh interval in every accumulator.
  for (const auto& [name, h] : pool.stage_snapshots()) {
    CHECK(h.count == 0 && h.total == 0.0 && h.buckets.empty());
  }

  const HistSnapshot& env_rtt = stages.at("actor.env_rtt_s");
  const HistSnapshot& step = stages.at("actor.env_step_s");
  const HistSnapshot& down = stages.at("actor.env_wire_down_s");
  const HistSnapshot& up = stages.at("actor.env_wire_up_s");
  CHECK(step.min >= kStepMs * 1e-3);
  CHECK(down.min > 0.0 && up.min > 0.0);
  double parts = down.total + step.total + up.total;
  CHECK(std::abs(parts - env_rtt.total) <= 1e-6 * env_rtt.total);

  const HistSnapshot& wake = stages.at("actor.reply_wake_s");
  const HistSnapshot& own = stages.at("actor.own_s");
  const HistSnapshot& cycle = stages.at("actor.cycle_s");
  CHECK(wake.min >= kHoldMs * 1e-3);
  // request_rtt_s ended before the hold: a cycle is the step, the hold
  // and little else, and the sum below would pass it otherwise.
  double sum = rtt.total + wake.total + own.total + env_rtt.total;
  CHECK(sum <= cycle.total);
  CHECK(cycle.total - sum <= 0.01 * cycle.total);
  CHECK(pool.telemetry().env_clock_unshared == 0);

  batcher->close();
  learner_queue->close();
  pool_thread.join();
  server.stop();
  server_thread.join();
  server.join_all();
  std::printf("actor cycle ok\n");
}

int main(int argc, char** argv) {
  // Optional substring filter (argv[1]): run only matching tests. Lets
  // the sanitizer smoke tests exercise the codec/queue paths in
  // sandboxes where the socket tests cannot run (scripts/build_native.sh
  // --sanitize=... --filter=...; tests/test_native.py uses it).
  const char* filter = argc > 1 ? argv[1] : nullptr;
  auto want = [filter](const char* name) {
    return filter == nullptr || std::strstr(name, filter) != nullptr;
  };
  int ran = 0;
  if (want("array")) { test_array_concat_slice(); ++ran; }
  if (want("nest")) { test_nest_ops(); ++ran; }
  if (want("wire_roundtrip")) { test_wire_roundtrip(); ++ran; }
  if (want("wire_malformed")) { test_wire_malformed(); ++ran; }
  if (want("batching_queue")) { test_batching_queue(); ++ran; }
  if (want("batching_queue_timeout")) { test_batching_queue_timeout_zero(); ++ran; }
  if (want("batching_queue_dequeue_item")) { test_batching_queue_dequeue_item(); ++ran; }
  if (want("queue_stress")) { test_queue_stress(); ++ran; }
  if (want("dynamic_batcher")) { test_dynamic_batcher(); ++ran; }
  if (want("batcher_telemetry")) { test_batcher_telemetry(); ++ran; }
  if (want("batcher_reply_instant")) { test_batcher_reply_instant(); ++ran; }
  if (want("routing_hash")) { test_routing_hash(); ++ran; }
  if (want("routing_slice")) { test_slice_router(); ++ran; }
  if (want("routing_replica")) { test_replica_router(); ++ran; }
  if (want("routing_replica_shed")) { test_replica_router_shed(); ++ran; }
  if (want("queue_try_dequeue")) { test_try_dequeue_upto(); ++ran; }
  if (want("batcher_continuous")) { test_continuous_batcher(); ++ran; }
  if (want("shm_ring_roundtrip")) { test_shm_ring_roundtrip(); ++ran; }
  if (want("shm_ring_adaptive_recheck")) { test_shm_ring_adaptive_recheck(); ++ran; }
  if (want("shm_ring_corrupt")) { test_shm_ring_corrupt(); ++ran; }
  if (want("shm_ring_transport")) { test_shm_ring_transport(); ++ran; }
  if (want("shm_ring_stress")) { test_shm_ring_stress(); ++ran; }
  if (want("env_server")) { test_env_server(); ++ran; }
  if (want("actor_cycle")) { test_actor_cycle(); ++ran; }
  if (ran == 0) {
    std::fprintf(stderr, "no tests match filter '%s'\n", filter);
    return 1;
  }
  if (filter == nullptr) {
    std::printf("ALL NATIVE CORE TESTS PASSED\n");
  } else {
    std::printf("%d FILTERED NATIVE CORE TESTS PASSED (filter '%s')\n",
                ran, filter);
  }
  return 0;
}
