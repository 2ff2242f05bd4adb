// A heartbeat that needs no interpreter lock (PR 26; see instruments.py).
// One C thread ticks every 5 ms and records, with CLOCK_MONOTONIC times:
//   kind 1: its own nanosleep came back > 100 ms late (the whole process, or the sandbox, stood still)
//   kind 2: an anonymous mmap + first touch + munmap of 256 KiB took > 50 ms (the address space's lock was held)
//   kind 3: the Python heartbeat's counter did not move for > 300 ms (begin), kind 4: it moved again (value = length)
#include <pthread.h>
#include <stdio.h>
#include <string.h>
#include <sys/mman.h>
#include <time.h>
#include <dirent.h>
#include <stdlib.h>
extern "C" {
struct ev { double t; double v; int kind; };
static ev evs[65536];
static volatile int n_ev = 0;
static volatile long py_beat = 0;
static volatile int stop_flag = 0;
static pthread_t th;
static double max_sleep = 0, max_mm = 0;
static long ticks = 0;
static char states[262144]; static int n_states = 0;
static double now() { timespec ts; clock_gettime(CLOCK_MONOTONIC, &ts); return ts.tv_sec + ts.tv_nsec * 1e-9; }
static void rec(int kind, double t, double v) { int i = n_ev; if (i < 65536) { evs[i].t = t; evs[i].v = v; evs[i].kind = kind; n_ev = i + 1; } }
// every thread's scheduler state (R running, S sleeping, D uninterruptible) and kernel wait channel, as /proc gives them
static void snapshot(double t) {
  DIR* d = opendir("/proc/self/task"); if (!d) return;
  n_states += snprintf(states + n_states, sizeof(states) - n_states, "@%.4f", t);
  dirent* e;
  while ((e = readdir(d)) && n_states < (int)sizeof(states) - 256) {
    if (e->d_name[0] == '.') continue;
    char p[96], buf[512]; snprintf(p, sizeof p, "/proc/self/task/%s/stat", e->d_name);
    FILE* f = fopen(p, "r"); if (!f) continue;
    size_t k = fread(buf, 1, sizeof buf - 1, f); fclose(f); buf[k] = 0;
    char* r = strrchr(buf, ')'); char st = (r && r[1] == ' ') ? r[2] : '?';
    char w[64] = ""; snprintf(p, sizeof p, "/proc/self/task/%s/wchan", e->d_name);
    f = fopen(p, "r"); if (f) { k = fread(w, 1, sizeof w - 1, f); fclose(f); w[k] = 0; for (char* c = w; *c; c++) if (*c == '\n' || *c == '"' || *c == '\\') *c = ' '; }
    n_states += snprintf(states + n_states, sizeof(states) - n_states, " %s:%c:%s", e->d_name, st, w);
  }
  closedir(d);
  n_states += snprintf(states + n_states, sizeof(states) - n_states, ";");
}
static void* loop(void*) {
  long last = py_beat; double moved = now(); int stalled = 0; double snapped = 0;
  while (!stop_flag) {
    double t0 = now(); timespec d = {0, 5000000}; nanosleep(&d, 0); double t1 = now();
    double g = t1 - t0 - 0.005; if (g > max_sleep) max_sleep = g; if (g > 0.1) rec(1, t1, g);
    void* p = mmap(0, 262144, PROT_READ | PROT_WRITE, MAP_PRIVATE | MAP_ANONYMOUS, -1, 0);
    if (p != MAP_FAILED) { ((volatile char*)p)[0] = 1; munmap(p, 262144); }
    double t2 = now(); if (t2 - t1 > max_mm) max_mm = t2 - t1; if (t2 - t1 > 0.05) rec(2, t2, t2 - t1);
    long b = py_beat;
    if (b != last) { if (stalled) { rec(4, t2, t2 - moved); stalled = 0; } last = b; moved = t2; }
    else if (!stalled && b > 0 && t2 - moved > 0.3) { rec(3, t2, t2 - moved); stalled = 1; snapshot(t2); snapped = t2; }
    else if (stalled && t2 - snapped > 0.5) { snapshot(t2); snapped = t2; }
    ticks++;
  }
  return 0;
}
void hb_start() { stop_flag = 0; n_ev = 0; n_states = 0; states[0] = 0; max_sleep = max_mm = 0; ticks = 0; py_beat = 0; pthread_create(&th, 0, loop, 0); }
void hb_beat() { py_beat++; }
double hb_now() { return now(); }
void hb_stop() { stop_flag = 1; pthread_join(th, 0); }
int hb_dump(const char* path) {
  FILE* f = fopen(path, "w"); if (!f) return -1;
  fprintf(f, "{\"ticks\": %ld, \"max_sleep_late_s\": %.4f, \"max_mm_s\": %.4f, \"events\": [", ticks, max_sleep, max_mm);
  for (int i = 0; i < n_ev; i++) fprintf(f, "%s[%d, %.4f, %.4f]", i ? ", " : "", evs[i].kind, evs[i].t, evs[i].v);
  fprintf(f, "], \"thread_states\": \"%s\"}\n", states); fclose(f); return n_ev;
}
}
